"""Tier-1 tests for arealint v3's SPMD/sharding-safety families
(docs/static_analysis.md "SPMD rules"):

1. **Mesh model** — the axis catalog parsed from parallel/mesh.py (ast,
   never imported) matches the tuple ``make_mesh`` actually builds at
   runtime, so catalog drift fails loudly.
2. **Rule fixtures** — every new rule has at least one positive fixture
   (fires on the bug) and one negative (quiet on the idiom / on an
   unresolvable pattern: propagation degrades, never guesses).
3. **Runtime twin** — ``logical_to_pspec``/``param_shardings`` raise on
   logical-axis typos instead of silently replicating.
4. **--changed-only** — the CI fast path scans exactly what passing the
   surviving files as explicit paths would scan, and a 3-file diff
   parses those files and the rules' catalogs, not the tree.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.arealint import (  # noqa: E402
    Config,
    MeshModel,
    PROJECT_RULES,
    RULES,
    parse_mesh_module,
    scan_source,
    scan_sources,
)

pytestmark = pytest.mark.arealint

MESH = MeshModel(
    axes=("data", "fsdp", "ctx", "model"),
    logical_rules={"embed": "fsdp", "heads": "model", "layer": None},
)
CFG = Config(mesh=MESH)


def rules_of(src, path="areal_tpu/some/module.py", rules=None):
    return [
        f.rule for f in scan_source(src, path, rules=rules, config=CFG)
    ]


def findings_of(src, path="areal_tpu/some/module.py", rules=None):
    return scan_source(src, path, rules=rules, config=CFG)


def project_of(sources, rules):
    return scan_sources(sources, rules=rules, config=CFG)


# ------------------------------------------------------------------ #
# mesh model provenance
# ------------------------------------------------------------------ #


class TestMeshModel:
    def test_parsed_axes_match_runtime_make_mesh(self):
        """The statically-parsed axis catalog IS the tuple make_mesh
        builds — if someone renames/reorders mesh axes, this fails and
        forces the catalog (and every spec in the tree) to follow."""
        parsed = parse_mesh_module(
            os.path.join(REPO, "areal_tpu", "parallel", "mesh.py")
        )
        assert parsed is not None

        from areal_tpu.parallel.mesh import ParallelConfig, make_mesh

        mesh = make_mesh(ParallelConfig())  # 1x1x1x1: any device count
        assert parsed.axes == tuple(mesh.axis_names)

    def test_parsed_logical_rules_match_runtime(self):
        from areal_tpu.parallel.mesh import DEFAULT_RULES

        parsed = parse_mesh_module(
            os.path.join(REPO, "areal_tpu", "parallel", "mesh.py")
        )
        assert parsed.logical_rules == DEFAULT_RULES

    def test_default_config_carries_the_model(self):
        cfg = Config.from_repo()
        assert cfg.mesh is not None
        assert cfg.mesh.axes == ("data", "fsdp", "ctx", "model")

    def test_unparsable_module_degrades_to_none(self, tmp_path):
        p = tmp_path / "mesh.py"
        p.write_text("def make_mesh():\n    return None\n")
        assert parse_mesh_module(p) is None
        p.write_text("def f(:\n")  # syntax error
        assert parse_mesh_module(p) is None

    def test_falls_back_to_module_level_mesh_call(self, tmp_path):
        """Review regression: a make_mesh without a literal axis tuple
        must not mask a module-level Mesh(...) literal."""
        p = tmp_path / "mesh.py"
        p.write_text(textwrap.dedent(
            """
            AXES = ("data", "model")

            def make_mesh(devs):
                return Mesh(devs, AXES)

            _DEFAULT = Mesh(None, ("data", "model"))
            """
        ))
        parsed = parse_mesh_module(p)
        assert parsed is not None and parsed.axes == ("data", "model")


# ------------------------------------------------------------------ #
# unknown-mesh-axis
# ------------------------------------------------------------------ #


class TestUnknownMeshAxis:
    def test_fires_on_typo_including_tuple_entries(self):
        src = textwrap.dedent(
            """
            from jax.sharding import NamedSharding, PartitionSpec as P

            a = NamedSharding(mesh, P("modle"))
            b = P(None, ("data", "fspd"))
            """
        )
        fs = findings_of(src, rules=["unknown-mesh-axis"])
        assert [f.line for f in fs] == [4, 5]
        assert "'modle'" in fs[0].message and "data, fsdp" in fs[0].message

    def test_quiet_on_valid_axes_and_dynamic_entries(self):
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            a = P(("data", "fsdp"), "ctx")
            b = P(None, axis_var, "model")     # dynamic entry skipped
            c = P(*computed)                   # fully dynamic
            """
        )
        assert rules_of(src, rules=["unknown-mesh-axis"]) == []

    def test_degrades_without_a_mesh_model(self):
        src = (
            "from jax.sharding import PartitionSpec as P\n"
            "a = P('definitely_wrong')\n"
        )
        fs = scan_source(
            src, "areal_tpu/x.py", rules=["unknown-mesh-axis"],
            config=Config(),  # no mesh catalog: degrade, never guess
        )
        assert fs == []

    def test_suppression_with_reason(self):
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            # arealint: ok(spec for the bench-only toy mesh)
            a = P("rows")
            """
        )
        assert rules_of(src, rules=["unknown-mesh-axis"]) == []


# ------------------------------------------------------------------ #
# mesh-axis-reuse
# ------------------------------------------------------------------ #


class TestMeshAxisReuse:
    def test_fires_on_reuse_direct_and_through_tuple(self):
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P

            a = P("model", None, "model")
            b = P(("data", "fsdp"), "data")
            """
        )
        fs = findings_of(src, rules=["mesh-axis-reuse"])
        assert [f.line for f in fs] == [4, 5]

    def test_quiet_on_distinct_axes(self):
        src = (
            "from jax.sharding import PartitionSpec as P\n"
            "a = P(('data', 'fsdp'), 'ctx', 'model')\n"
        )
        assert rules_of(src, rules=["mesh-axis-reuse"]) == []


# ------------------------------------------------------------------ #
# shard-map-spec-arity
# ------------------------------------------------------------------ #


class TestShardMapArity:
    def test_fires_on_signature_mismatch(self):
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def body(q, k, v):
                return q

            def run(mesh, q, k, v):
                f = shard_map(
                    body, mesh=mesh,
                    in_specs=(P("ctx"), P("ctx")),
                    out_specs=P("ctx"),
                )
                return f(q, k)
            """
        )
        fs = findings_of(src, rules=["shard-map-spec-arity"])
        assert len(fs) == 1
        assert "2 entries but body() takes 3" in fs[0].message

    def test_fires_on_invocation_mismatch_when_body_unresolvable(self):
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def run(mesh, external_fn, q, k, v):
                return shard_map(
                    external_fn, mesh=mesh,
                    in_specs=(P("ctx"), P("ctx")),
                    out_specs=P("ctx"),
                )(q, k, v)
            """
        )
        fs = findings_of(src, rules=["shard-map-spec-arity"])
        assert len(fs) == 1 and "passes 3 operand(s)" in fs[0].message

    def test_fires_on_out_specs_vs_return_tuple(self):
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def body(q, k):
                return q, k

            def run(mesh, q, k):
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(P("ctx"), P("ctx")),
                    out_specs=(P("ctx"), P("ctx"), P("ctx")),
                )(q, k)
            """
        )
        fs = findings_of(src, rules=["shard-map-spec-arity"])
        assert len(fs) == 1
        assert "out_specs has 3 entries but body() returns a 2-tuple" in (
            fs[0].message
        )

    def test_quiet_on_correct_arity_partial_and_shadowed_names(self):
        src = textwrap.dedent(
            """
            import functools
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def _shard(q, k, v, seg, *, scale):
                return q

            def scan_user(q):
                def body(carry, x):      # unrelated 2-arg scan body
                    return carry, x
                return body

            def run(mesh, q, k, v, seg):
                fn = functools.partial(_shard, scale=1.0)
                out = shard_map(
                    fn, mesh=mesh,
                    in_specs=(P("ctx"), P("ctx"), P("ctx"), P("ctx")),
                    out_specs=P("ctx"),
                )(q, k, v, seg)
                # `body` here is a local VARIABLE shadowing the scan
                # body def above — resolution must degrade, not match
                body = functools.partial(_shard, scale=2.0)
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(P("ctx"), P("ctx"), P("ctx"), P("ctx")),
                    out_specs=P("ctx"),
                )(q, k, v, seg)
            """
        )
        assert rules_of(src, rules=["shard-map-spec-arity"]) == []

    def test_callable_parameter_never_resolves_to_module_def(self):
        """Review regression: a callable PARAMETER named like an
        unrelated module-level def must degrade, not resolve."""
        src = textwrap.dedent(
            """
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def kernel(a, b, c):
                return a

            def outer(kernel, mesh, x):
                return shard_map(
                    kernel, mesh=mesh,
                    in_specs=(P("data"),),
                    out_specs=P("data"),
                )(x)
            """
        )
        assert rules_of(src, rules=["shard-map-spec-arity"]) == []

    def test_partial_keyword_over_positional_param_degrades(self):
        """Review regression: binding a POSITIONAL-or-keyword param by
        keyword shrinks the callable's positional surface in a way
        subtraction can't model — must degrade, not fire."""
        src = textwrap.dedent(
            """
            import functools
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def body(q, k, scale):
                return q

            def run(mesh, q, k):
                return shard_map(
                    functools.partial(body, scale=0.5), mesh=mesh,
                    in_specs=(P("ctx"), P("ctx")),
                    out_specs=P("ctx"),
                )(q, k)
            """
        )
        assert rules_of(src, rules=["shard-map-spec-arity"]) == []

    def test_partial_positional_args_reduce_arity(self):
        src = textwrap.dedent(
            """
            import functools
            from jax.sharding import PartitionSpec as P
            from jax.experimental.shard_map import shard_map

            def body(cfg, q, k):
                return q

            def run(mesh, cfg, q, k):
                return shard_map(
                    functools.partial(body, cfg), mesh=mesh,
                    in_specs=(P("ctx"), P("ctx"), P("ctx")),
                    out_specs=P("ctx"),
                )(q, k)
            """
        )
        fs = findings_of(src, rules=["shard-map-spec-arity"])
        assert len(fs) == 1 and "takes 2 positional" in fs[0].message


# ------------------------------------------------------------------ #
# donation-sharding-mismatch
# ------------------------------------------------------------------ #


class TestDonationShardingMismatch:
    SRC = textwrap.dedent(
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def train(mesh, params, batch):
            sh_p = NamedSharding(mesh, P("fsdp"))
            sh_r = NamedSharding(mesh, P())
            params = jax.device_put(params, sh_p)
            step = jax.jit(
                train_step, donate_argnums=(0,), out_shardings=(OUT,)
            )
            return step(params, batch)
        """
    )

    def test_fires_when_no_output_matches_donated_sharding(self):
        fs = findings_of(
            self.SRC.replace("OUT", "sh_r"),
            rules=["donation-sharding-mismatch"],
        )
        assert len(fs) == 1 and fs[0].severity == "warn"
        assert "'params'" in fs[0].message

    def test_quiet_when_an_output_matches(self):
        assert rules_of(
            self.SRC.replace("OUT", "sh_p"),
            rules=["donation-sharding-mismatch"],
        ) == []

    def test_degrades_on_unresolvable_out_entry(self):
        # None entry = "let XLA choose": the output COULD alias
        assert rules_of(
            self.SRC.replace("OUT", "None"),
            rules=["donation-sharding-mismatch"],
        ) == []


# ------------------------------------------------------------------ #
# hot-path-reshard (propagation lite)
# ------------------------------------------------------------------ #


class TestHotPathReshard:
    def test_fires_inside_hot_root(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/step.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                def step(mesh, batch):  # arealint: hot
                    sh_b = NamedSharding(mesh, P(("data", "fsdp")))
                    sh_r = NamedSharding(mesh, P())
                    x = jax.device_put(batch, sh_b)
                    return jax.lax.with_sharding_constraint(x, sh_r)
                """
            ),
        }, rules=["hot-path-reshard"])
        assert [f.rule for f in fs] == ["hot-path-reshard"]
        assert "'x'" in fs[0].message and "P()" in fs[0].message

    def test_fires_cross_module_from_hot_root(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/a.py": textwrap.dedent(
                """
                from pkg.b import helper

                def step(mesh, batch):  # arealint: hot
                    return helper(mesh, batch)
                """
            ),
            "pkg/b.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                def helper(mesh, batch):
                    x = jax.device_put(
                        batch, NamedSharding(mesh, P("data"))
                    )
                    return jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, P("model"))
                    )
                """
            ),
        }, rules=["hot-path-reshard"])
        assert [(f.path, f.rule) for f in fs] == [
            ("pkg/b.py", "hot-path-reshard")
        ]
        assert "step" in fs[0].message  # names the hot root

    def test_quiet_off_hot_path_and_on_unresolved_specs(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/cold.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                def cold(mesh, batch):
                    x = jax.device_put(
                        batch, NamedSharding(mesh, P("data"))
                    )
                    return jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, P())
                    )

                def hot(mesh, batch, sh):  # arealint: hot
                    # operand spec unknown -> constraint establishes,
                    # not reshards; dynamic sharding arg -> degrade
                    y = jax.lax.with_sharding_constraint(batch, sh)
                    return jax.lax.with_sharding_constraint(
                        y, NamedSharding(mesh, P("data"))
                    )
                """
            ),
        }, rules=["hot-path-reshard"])
        assert fs == []

    def test_suppression_with_reason(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/step.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                def step(mesh, batch):  # arealint: hot
                    sh_b = NamedSharding(mesh, P(("data", "fsdp")))
                    sh_r = NamedSharding(mesh, P())
                    x = jax.device_put(batch, sh_b)
                    # arealint: ok(one deliberate all-gather for sampling)
                    return jax.lax.with_sharding_constraint(x, sh_r)
                """
            ),
        }, rules=["hot-path-reshard"])
        assert fs == []

    def test_attr_rebound_to_unresolvable_value_degrades(self):
        """Review regression: a self-attr with one literal NamedSharding
        binding AND one opaque rebinding (a forwarded parameter) has an
        unknowable spec — it must not anchor a reshard finding."""
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/eng.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                class Eng:
                    def __init__(self, mesh):
                        self._sh = NamedSharding(mesh, P("model"))

                    def set_sharding(self, sh):
                        self._sh = sh          # opaque rebinding

                    def step(self, mesh, x):  # arealint: hot
                        x = jax.device_put(
                            x, NamedSharding(mesh, P("data"))
                        )
                        return jax.device_put(x, self._sh)
                """
            ),
        }, rules=["hot-path-reshard"])
        assert fs == []

    def test_rebind_through_unmodeled_forms_invalidates(self):
        """Review regression: AnnAssign/AugAssign/for/with rebinds drop
        the inferred spec — a constraint on the FRESH value is not a
        reshard of the old one."""
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/step.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                def step(mesh, batch, items):  # arealint: hot
                    sh_b = NamedSharding(mesh, P("data"))
                    sh_r = NamedSharding(mesh, P())
                    x = jax.device_put(batch, sh_b)
                    x: object = compute(batch)       # annotated rebind
                    a = jax.device_put(batch, sh_b)
                    a += 1                           # augmented rebind
                    for b in items:                  # loop rebind
                        pass
                    y1 = jax.lax.with_sharding_constraint(x, sh_r)
                    y2 = jax.lax.with_sharding_constraint(a, sh_r)
                    return y1, y2
                """
            ),
        }, rules=["hot-path-reshard"])
        assert fs == []


# ------------------------------------------------------------------ #
# jit-sharding-disagreement
# ------------------------------------------------------------------ #


class TestJitShardingDisagreement:
    def test_fires_when_sites_disagree(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/f.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                @jax.jit
                def apply(x):
                    return x

                def a(mesh, v):
                    v = jax.device_put(v, NamedSharding(mesh, P("data")))
                    return apply(v)

                def b(mesh, v):
                    v = jax.device_put(v, NamedSharding(mesh, P("model")))
                    return apply(v)
                """
            ),
        }, rules=["jit-sharding-disagreement"])
        # one defect ("pick one sharding"), ONE finding — the sibling
        # site is named in the message, not double-reported
        assert len(fs) == 1 and fs[0].severity == "warn"
        assert "P('model')" in fs[0].message or "P('data')" in fs[0].message

    def test_quiet_when_sites_agree_or_specs_unknown(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/f.py": textwrap.dedent(
                """
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                @jax.jit
                def apply(x):
                    return x

                def a(mesh, v):
                    v = jax.device_put(v, NamedSharding(mesh, P("data")))
                    return apply(v)

                def b(mesh, v):
                    v = jax.device_put(v, NamedSharding(mesh, P("data")))
                    return apply(v)

                def c(v):
                    return apply(v)   # unknown spec: degrade
                """
            ),
        }, rules=["jit-sharding-disagreement"])
        assert fs == []


# ------------------------------------------------------------------ #
# host-divergence-collective
# ------------------------------------------------------------------ #

MULTIHOST_FIXTURE = textwrap.dedent(
    """
    from jax.experimental import multihost_utils

    def barrier(name="b"):
        multihost_utils.sync_global_devices(name)

    def main_decides(flag):
        return flag
    """
)


class TestHostDivergence:
    def test_fires_on_time_branch_guarding_collective(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/multihost.py": MULTIHOST_FIXTURE,
            "pkg/loop.py": textwrap.dedent(
                """
                import time
                from pkg import multihost

                def train(deadline):
                    if time.monotonic() > deadline:
                        multihost.barrier()
                """
            ),
        }, rules=["host-divergence-collective"])
        assert [f.rule for f in fs] == ["host-divergence-collective"]
        assert "time.monotonic()" in fs[0].message
        assert "multihost.barrier()" in fs[0].message

    def test_quiet_when_gated_through_main_decides(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/multihost.py": MULTIHOST_FIXTURE,
            "pkg/loop.py": textwrap.dedent(
                """
                import time
                from pkg import multihost

                def train(deadline):
                    if multihost.main_decides(
                        time.monotonic() > deadline
                    ):
                        multihost.barrier()
                """
            ),
        }, rules=["host-divergence-collective"])
        assert fs == []

    def test_fires_through_cross_module_return_taint(self):
        """is_main()-style: the divergent value flows through a helper's
        RETURN, across a module boundary, into the branch."""
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/multihost.py": MULTIHOST_FIXTURE,
            "pkg/timerlib.py": textwrap.dedent(
                """
                import time

                def expired(deadline):
                    return time.monotonic() > deadline
                """
            ),
            "pkg/loop.py": textwrap.dedent(
                """
                from pkg import multihost
                from pkg.timerlib import expired

                def train(deadline):
                    flag = expired(deadline)
                    if flag:
                        multihost.barrier()
                """
            ),
        }, rules=["host-divergence-collective"])
        assert len(fs) == 1 and fs[0].path == "pkg/loop.py"
        assert "expired()" in fs[0].message

    def test_fires_on_control_dependent_taint(self):
        """The EpochStepTimeFreqCtl.check() shape: the returned flag is
        a CONSTANT assigned under a time-divergent branch."""
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/multihost.py": MULTIHOST_FIXTURE,
            "pkg/timerlib.py": textwrap.dedent(
                """
                import time

                class Timer:
                    def check(self):
                        fire = False
                        if time.monotonic() > self.next_at:
                            fire = True
                        return fire
                """
            ),
            "pkg/loop.py": textwrap.dedent(
                """
                from pkg import multihost
                from pkg.timerlib import Timer

                def train():
                    t = Timer()
                    if t.check():
                        multihost.barrier()
                """
            ),
        }, rules=["host-divergence-collective"])
        assert len(fs) == 1 and fs[0].path == "pkg/loop.py"

    def test_fires_on_process_index_guarding_jitted_psum(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/ops.py": textwrap.dedent(
                """
                import jax

                @jax.jit
                def reduce_all(x):
                    return jax.lax.psum(x, "data")
                """
            ),
            "pkg/loop.py": textwrap.dedent(
                """
                import jax
                from pkg.ops import reduce_all

                def step(x):
                    if jax.process_index() == 0:
                        return reduce_all(x)
                    return x
                """
            ),
        }, rules=["host-divergence-collective"])
        assert len(fs) == 1
        assert "process_index()" in fs[0].message
        assert "lax.psum()" in fs[0].message

    def test_fires_on_signal_poll_guarding_mesh_entry(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/loop.py": textwrap.dedent(
                """
                def run(mesh, shutdown):
                    if shutdown.should_stop():
                        with mesh:
                            pass
                """
            ),
        }, rules=["host-divergence-collective"])
        assert len(fs) == 1
        assert "mesh context entry" in fs[0].message

    def test_quiet_on_uniform_branch_and_collective_free_branch(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/multihost.py": MULTIHOST_FIXTURE,
            "pkg/loop.py": textwrap.dedent(
                """
                import time
                from pkg import multihost

                def train(step, total, log, deadline):
                    if step % 10 == 0:          # host-uniform test
                        multihost.barrier()
                    if time.monotonic() > deadline:
                        log.info("late")        # no collective guarded
                """
            ),
        }, rules=["host-divergence-collective"])
        assert fs == []

    def test_suppression_with_reason(self):
        fs = project_of({
            "pkg/__init__.py": "",
            "pkg/multihost.py": MULTIHOST_FIXTURE,
            "pkg/loop.py": textwrap.dedent(
                """
                import time
                from pkg import multihost

                def train(deadline):
                    # arealint: ok(single-process tool, never on a pod)
                    if time.monotonic() > deadline:
                        multihost.barrier()
                """
            ),
        }, rules=["host-divergence-collective"])
        assert fs == []


# ------------------------------------------------------------------ #
# runtime twin: logical-axis validation in mesh.py
# ------------------------------------------------------------------ #


class TestRuntimeLogicalAxisValidation:
    def test_typo_raises_instead_of_replicating(self):
        from areal_tpu.parallel.mesh import logical_to_pspec

        with pytest.raises(ValueError, match="vocag"):
            logical_to_pspec(("layer", "vocag"))

    def test_valid_axes_and_none_pass(self):
        from areal_tpu.parallel.mesh import logical_to_pspec

        spec = logical_to_pspec(("layer", "embed", "heads"))
        assert tuple(spec) == (None, "fsdp", "model")
        assert tuple(logical_to_pspec(None)) == ()

    def test_param_shardings_validates_tree_leaves(self):
        from areal_tpu.parallel.mesh import (
            ParallelConfig, make_mesh, param_shardings,
        )

        mesh = make_mesh(ParallelConfig())
        with pytest.raises(ValueError, match="embedd"):
            param_shardings(mesh, {"w": ("embedd",)})

    def test_custom_rules_still_validate(self):
        from areal_tpu.parallel.mesh import logical_to_pspec

        with pytest.raises(ValueError, match="embed"):
            logical_to_pspec(("embed",), rules={"tokens": "ctx"})


# ------------------------------------------------------------------ #
# registry + --changed-only
# ------------------------------------------------------------------ #


class TestRegistry:
    def test_spmd_families_registered(self):
        assert {"unknown-mesh-axis", "mesh-axis-reuse",
                "shard-map-spec-arity",
                "donation-sharding-mismatch"} <= set(RULES)
        assert {"hot-path-reshard", "jit-sharding-disagreement",
                "host-divergence-collective"} <= set(PROJECT_RULES)


class TestChangedOnly:
    def _run(self, *args, stdin=None):
        return subprocess.run(
            [sys.executable, "-m", "tools.arealint", *args],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            input=stdin,
        )

    def test_same_findings_as_explicit_paths(self, tmp_path):
        """The pinned property: --changed-only with a file list on
        stdin produces byte-identical findings to passing the SAME
        surviving files as explicit CLI paths."""
        bad = tmp_path / "bad.py"
        bad.write_text("import os\nx = os.environ.get('AREAL_X')\n")
        clean = tmp_path / "clean.py"
        clean.write_text("def f():\n    return 1\n")
        excluded = tmp_path / "excluded.py"  # NOT in the stdin list
        excluded.write_text("import os\ny = os.getenv('AREAL_Y')\n")
        gone = tmp_path / "gone.py"          # in the list, not on disk

        stdin = f"{bad}\n{clean}\n{gone}\nnot_python.txt\n"
        r_changed = self._run(
            str(tmp_path), "--changed-only", "--no-baseline",
            "--format", "json", stdin=stdin,
        )
        r_explicit = self._run(
            str(bad), str(clean), "--no-baseline", "--format", "json",
        )
        assert r_changed.returncode == r_explicit.returncode == 1
        changed = json.loads(r_changed.stdout)
        explicit = json.loads(r_explicit.stdout)
        assert changed["findings"] == explicit["findings"]
        assert changed["errors"] == 1
        # the excluded file's finding appears in neither
        assert all(
            "excluded.py" not in f["path"] for f in changed["findings"]
        )

    def test_outside_scan_set_is_dropped(self, tmp_path):
        inside = tmp_path / "scanned"
        inside.mkdir()
        bad = inside / "bad.py"
        bad.write_text("import os\nx = os.environ.get('AREAL_X')\n")
        outside = tmp_path / "other"
        outside.mkdir()
        also_bad = outside / "also_bad.py"
        also_bad.write_text("import os\ny = os.getenv('AREAL_Y')\n")
        r = self._run(
            str(inside), "--changed-only", "--no-baseline",
            "--format", "json", stdin=f"{bad}\n{also_bad}\n",
        )
        payload = json.loads(r.stdout)
        assert [os.path.basename(f["path"]) for f in payload["findings"]
                ] == ["bad.py"]

    def test_empty_diff_exits_clean(self):
        r = self._run("--changed-only", "--since", "HEAD", stdin="")
        assert r.returncode == 0
        assert "no changed Python files" in r.stdout
        assert "HEAD" in r.stdout

    def test_empty_diff_keeps_machine_formats_parseable(self):
        """Review regression: docs-only diffs must still emit the
        stable json/sarif documents, not a plain-text note."""
        r = self._run(
            "--changed-only", "--format", "json", stdin="README.md\n"
        )
        assert r.returncode == 0
        payload = json.loads(r.stdout)
        assert payload["findings"] == [] and payload["errors"] == 0
        r = self._run("--changed-only", "--format", "sarif", stdin="")
        assert r.returncode == 0
        log = json.loads(r.stdout)
        assert log["version"] == "2.1.0"
        assert log["runs"][0]["results"] == []

    def test_since_requires_changed_only(self):
        r = self._run("--since", "HEAD")
        assert r.returncode == 2

    def test_three_file_diff_under_two_seconds(self, monkeypatch):
        """What keeps a pre-commit scan of a three-file diff under two
        seconds, COUNTED and not timed (the clock of a machine that six
        test workers share says nothing about the scan): the run parses
        the three files and the modules the rules' catalogs are read from
        (``Config.from_repo``: a dozen), and nothing else of the tree."""
        import ast
        import io

        from tools.arealint import core
        from tools.arealint.__main__ import main

        files = [
            "areal_tpu/parallel/mesh.py",
            "areal_tpu/parallel/multihost.py",
            "areal_tpu/base/timeutil.py",
        ]
        parsed = []
        real_parse = ast.parse

        def counting_parse(source, filename="<unknown>", *args, **kw):
            parsed.append(os.path.relpath(str(filename), REPO))
            return real_parse(source, filename, *args, **kw)

        monkeypatch.setattr(ast, "parse", counting_parse)
        Config.from_repo()
        catalogs = set(parsed)
        parsed.clear()
        # the scan loads the catalogs itself, as a fresh process does
        monkeypatch.setattr(core, "_DEFAULT_CONFIG", None)
        monkeypatch.setattr(sys, "stdin", io.StringIO("\n".join(files) + "\n"))
        assert main(["--changed-only"]) == 0
        assert set(files) <= set(parsed) <= set(files) | catalogs
        tree = sum(
            name.endswith(".py")
            for top in ("areal_tpu", "tools", "tests")
            for _, _, names in os.walk(os.path.join(REPO, top))
            for name in names)
        assert 10 * len(set(parsed)) < tree
