"""Tier-1 tests for tools/arealint — the repo's static-analysis framework
(docs/static_analysis.md).

Three layers:

1. **Rule fixtures** — every JAX/TPU rule has at least one positive
   fixture (it fires on the bug pattern) and one negative fixture (it
   stays quiet on the idiomatic pattern).
2. **Framework semantics** — inline suppressions require reasons,
   baseline entries suppress exactly their findings and expire (report
   stale) when the violation is fixed, severities split errors/warns.
3. **The tree itself** — ``areal_tpu/`` stays clean at error severity
   (warn findings are reported but non-fatal), and the CLI exit codes
   are stable (0 clean / 1 errors / 2 usage).
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
    RULES,
    apply_baseline,
    has_errors,
    scan_paths,
    scan_source,
)

pytestmark = pytest.mark.arealint

# Fixture scans use an explicit empty-catalog Config so catalog rules
# behave deterministically regardless of the repo checkout state.
FIXTURE_CFG = Config(
    counter_values=frozenset({"ft/evictions", "fwd_pipe/dispatched"}),
    counter_names=frozenset({"FT_EVICTIONS", "PIPE_FWD_DISPATCHED"}),
    fault_points=frozenset({"gen.http", "train.step"}),
)


def rules_of(src, path="areal_tpu/some/module.py", rules=None):
    return [
        f.rule
        for f in scan_source(src, path, rules=rules, config=FIXTURE_CFG)
    ]


def findings_of(src, path="areal_tpu/some/module.py", rules=None):
    return scan_source(src, path, rules=rules, config=FIXTURE_CFG)


# ------------------------------------------------------------------ #
# host-sync-in-hot-path
# ------------------------------------------------------------------ #


class TestHostSyncRule:
    def test_fires_inside_hot_annotated_function(self):
        src = textwrap.dedent(
            """
            import jax

            def step(batch):  # arealint: hot
                out = dispatch(batch)
                loss = float(fetch(out))
                return out.grads.item()
            """
        )
        rules = rules_of(src, rules=["host-sync-in-hot-path"])
        assert rules == ["host-sync-in-hot-path"] * 2

    def test_fires_transitively_through_call_graph(self):
        src = textwrap.dedent(
            """
            import jax

            def outer(batch):  # arealint: hot
                return helper(batch)

            def helper(batch):
                return jax.device_get(batch)
            """
        )
        fs = findings_of(src, rules=["host-sync-in-hot-path"])
        assert [f.rule for f in fs] == ["host-sync-in-hot-path"]
        assert "helper()" in fs[0].message

    def test_fires_inside_jitted_function(self):
        src = textwrap.dedent(
            """
            import jax

            def build():
                def step(x):
                    return x.sum().item()
                return jax.jit(step)
            """
        )
        assert rules_of(src, rules=["host-sync-in-hot-path"]) == [
            "host-sync-in-hot-path"
        ]

    def test_quiet_off_the_hot_path_and_on_host_scalars(self):
        src = textwrap.dedent(
            """
            import jax
            import numpy as np

            def cold_eval(batch):
                # not hot-annotated, not jitted, not reachable from hot
                return jax.device_get(batch)

            def hot_driver(batch):  # arealint: hot
                w = float(total)          # float(name): host scalar
                arr = np.asarray(rows)    # np.asarray(name): host data
                return w, arr
            """
        )
        assert rules_of(src, rules=["host-sync-in-hot-path"]) == []

    def test_ok_annotation_with_reason_suppresses(self):
        src = textwrap.dedent(
            """
            import jax

            def step(batch):  # arealint: hot
                # arealint: ok(single deferred stats pull per interval)
                return jax.device_get(batch)
            """
        )
        assert rules_of(src, rules=["host-sync-in-hot-path"]) == []


# ------------------------------------------------------------------ #
# retrace-hazard
# ------------------------------------------------------------------ #


class TestRetraceRule:
    def test_fires_on_jit_in_loop(self):
        src = textwrap.dedent(
            """
            import jax

            def sweep(fns, xs):
                outs = []
                for fn in fns:
                    outs.append(jax.jit(fn)(xs))
                return outs
            """
        )
        rules = rules_of(src, rules=["retrace-hazard"])
        assert "retrace-hazard" in rules

    def test_fires_on_immediate_invoke(self):
        src = textwrap.dedent(
            """
            import jax

            def step(params, x):
                return jax.jit(apply)(params, x)
            """
        )
        fs = findings_of(src, rules=["retrace-hazard"])
        assert len(fs) == 1 and "immediately invoked" in fs[0].message

    def test_fires_on_nonhashable_static_operand(self):
        src = textwrap.dedent(
            """
            import jax

            def run(x):
                return jax.jit(f, static_argnums=(1,))(x, [1, 2, 3])
            """
        )
        msgs = [f.message for f in findings_of(src, rules=["retrace-hazard"])]
        assert any("non-hashable operand" in m for m in msgs)

    def test_fires_on_closure_captured_jnp_array(self):
        src = textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp

            def build(cfg):
                table = jnp.arange(1024)

                def step(x):
                    return x + table

                return jax.jit(step)
            """
        )
        msgs = [f.message for f in findings_of(src, rules=["retrace-hazard"])]
        assert any("closes over jnp array 'table'" in m for m in msgs)

    def test_one_finding_for_immediate_invoke_inside_loop(self):
        src = textwrap.dedent(
            """
            import jax

            def sweep(xs):
                for x in xs:
                    y = jax.jit(apply)(x)
                return y
            """
        )
        fs = findings_of(src, rules=["retrace-hazard"])
        assert len(fs) == 1 and "inside a loop" in fs[0].message

    def test_quiet_on_cached_module_level_and_assigned_jit(self):
        src = textwrap.dedent(
            """
            import jax

            jitted = jax.jit(apply)

            def build(self):
                if "k" not in self._cache:
                    self._cache["k"] = jax.jit(apply)
                return self._cache["k"]

            def step(params, x):
                fn = jax.jit(apply, static_argnums=(1,))
                return fn
            """
        )
        assert rules_of(src, rules=["retrace-hazard"]) == []

    def test_is_warn_severity(self):
        src = "import jax\ndef f(x):\n    return jax.jit(g)(x)\n"
        fs = findings_of(src, rules=["retrace-hazard"])
        assert fs and all(f.severity == "warn" for f in fs)
        assert not has_errors(fs)


# ------------------------------------------------------------------ #
# donation-after-use
# ------------------------------------------------------------------ #


class TestDonationRule:
    def test_fires_on_read_after_donating_call(self):
        src = textwrap.dedent(
            """
            import jax

            def train(params, opt_state, batch):
                step = jax.jit(train_step, donate_argnums=(0, 1))
                new_params, new_opt = step(params, opt_state, batch)
                norm = global_norm(params)   # donated buffer!
                return new_params, new_opt, norm
            """
        )
        fs = findings_of(src, rules=["donation-after-use"])
        assert [f.rule for f in fs] == ["donation-after-use"]
        assert "'params'" in fs[0].message

    def test_fires_for_immediate_invoke_donation(self):
        src = textwrap.dedent(
            """
            import jax

            def train(params, batch):
                out = jax.jit(train_step, donate_argnums=(0,))(params, batch)
                return params.mean(), out
            """
        )
        assert rules_of(src, rules=["donation-after-use"]) == [
            "donation-after-use"
        ]

    def test_quiet_when_rebound_at_call_or_before_use(self):
        src = textwrap.dedent(
            """
            import jax

            def train(self, batch):
                step = jax.jit(train_step, donate_argnums=(0, 1))
                # rebinding at the call keeps the names valid
                self.params, self.opt_state = step(
                    self.params, self.opt_state, batch
                )
                return global_norm(self.params)

            def other(params, batch):
                step = jax.jit(train_step, donate_argnums=(0,))
                out = step(params, batch)
                params = out          # rebound before any read
                return params
            """
        )
        assert rules_of(src, rules=["donation-after-use"]) == []


# ------------------------------------------------------------------ #
# env-knob
# ------------------------------------------------------------------ #


class TestEnvKnobRule:
    def test_fires_on_reads_outside_catalog(self):
        src = textwrap.dedent(
            """
            import os

            LEVEL = os.environ.get("AREAL_LOG_LEVEL", "INFO")
            DEPTH = os.getenv("AREAL_DEPTH")
            RAW = os.environ["AREAL_RAW"]
            HAS = "AREAL_X" in os.environ
            """
        )
        assert rules_of(src, rules=["env-knob"]) == ["env-knob"] * 4

    def test_fires_on_from_import_forms(self):
        src = textwrap.dedent(
            """
            from os import environ, getenv

            DEPTH = getenv("AREAL_DEPTH")
            RAW = environ["AREAL_RAW"]
            LEVEL = environ.get("AREAL_LOG_LEVEL", "INFO")
            HAS = "AREAL_X" in environ
            """
        )
        assert rules_of(src, rules=["env-knob"]) == ["env-knob"] * 4

    def test_quiet_in_catalog_and_env_helpers_and_on_writes(self):
        src = textwrap.dedent(
            """
            import os

            def log_level():
                return os.environ.get("AREAL_LOG_LEVEL", "INFO")
            """
        )
        assert rules_of(
            src, path="areal_tpu/base/constants.py", rules=["env-knob"]
        ) == []

        helper = textwrap.dedent(
            """
            import os

            def _env_float(name, default):
                raw = os.environ.get(name)
                return float(raw) if raw else default

            def not_a_helper():
                return os.environ.get("AREAL_X")
            """
        )
        rules = rules_of(
            helper, path="areal_tpu/system/worker_base.py",
            rules=["env-knob"],
        )
        assert rules == ["env-knob"]  # only the non-_env_* read

        writes = textwrap.dedent(
            """
            import os

            os.environ["AREAL_FILEROOT"] = "/tmp/x"
            os.environ.setdefault("AREAL_ROOT", "/tmp/y")
            os.environ.pop("JAX_PLATFORMS", None)
            """
        )
        assert rules_of(writes, rules=["env-knob"]) == []


# ------------------------------------------------------------------ #
# registry rules
# ------------------------------------------------------------------ #


class TestRegistryRules:
    def test_counter_literal_must_be_registered(self):
        src = textwrap.dedent(
            """
            from areal_tpu.base import metrics as metrics_mod

            metrics_mod.counters.add("ft/evictions")
            metrics_mod.counters.add("ft/not_in_catalog")
            metrics_mod.counters.peak("fwd_pipe/dispatched", 3)
            """
        )
        fs = findings_of(src, rules=["unregistered-counter"])
        assert len(fs) == 1 and "ft/not_in_catalog" in fs[0].message

    def test_counter_constant_must_be_defined(self):
        src = textwrap.dedent(
            """
            from areal_tpu.base import metrics as metrics_mod

            metrics_mod.counters.add(metrics_mod.FT_EVICTIONS)
            metrics_mod.counters.add(metrics_mod.FT_TYPO_NAME)
            metrics_mod.counters.get(local_variable_name)
            """
        )
        fs = findings_of(src, rules=["unregistered-counter"])
        assert len(fs) == 1 and "FT_TYPO_NAME" in fs[0].message

    def test_histogram_observe_must_be_registered(self):
        """The telemetry plane's histogram kind goes through the same
        catalog: counters.observe with an uncataloged key is flagged, a
        cataloged literal or constant passes, and the repo's REAL catalog
        carries the histogram constants (parsed, not imported)."""
        src = textwrap.dedent(
            """
            from areal_tpu.base import metrics as metrics_mod

            metrics_mod.counters.observe("ft/evictions", 1.0)
            metrics_mod.counters.observe("staleness_not_in_catalog", 2)
            metrics_mod.counters.observe(metrics_mod.FT_EVICTIONS, 3)
            """
        )
        fs = findings_of(src, rules=["unregistered-counter"])
        assert len(fs) == 1 and "staleness_not_in_catalog" in fs[0].message
        # the real catalog registers the trajectory histogram keys
        real = Config.from_repo()
        for name, value in [
            ("STALENESS_VERSIONS", "staleness_versions"),
            ("QUEUE_WAIT_S", "queue_wait_s"),
            ("E2E_LATENCY_S", "e2e_latency_s"),
        ]:
            assert name in real.counter_names
            assert value in real.counter_values

    def test_fault_point_must_be_registered(self):
        src = textwrap.dedent(
            """
            from areal_tpu.base import faults

            faults.maybe_fail("gen.http", url=url)
            faults.maybe_trip("train.step", step=3)
            faults.maybe_fail("gen.htpp", url=url)
            """
        )
        fs = findings_of(src, rules=["unregistered-fault-point"])
        assert len(fs) == 1 and "gen.htpp" in fs[0].message

    def test_registry_rules_skip_without_catalog(self):
        cfg = Config()  # no catalogs loaded
        src = 'counters.add("whatever")\nmaybe_fail("nope")\n'
        fs = scan_source(
            src, "areal_tpu/x.py",
            rules=["unregistered-counter", "unregistered-fault-point"],
            config=cfg,
        )
        assert fs == []


# ------------------------------------------------------------------ #
# suppression semantics
# ------------------------------------------------------------------ #


class TestSuppression:
    def test_reason_required(self):
        src = textwrap.dedent(
            """
            import os

            a = os.environ.get("AREAL_A")  # arealint: ok
            b = os.environ.get("AREAL_B")  # arealint: ok()
            c = os.environ.get("AREAL_C")  # arealint: ok(read by ops tooling)
            """
        )
        fs = findings_of(
            src, rules=["env-knob", "suppression-missing-reason"]
        )
        by_rule = {}
        for f in fs:
            by_rule.setdefault(f.rule, []).append(f.line)
        # the two reason-less suppressions do NOT suppress...
        assert by_rule["env-knob"] == [4, 5]
        # ...and are themselves flagged (warn)
        assert by_rule["suppression-missing-reason"] == [4, 5]

    def test_comment_line_above_suppresses(self):
        src = textwrap.dedent(
            """
            import os

            # arealint: ok(documented legacy read)
            a = os.environ.get("AREAL_A")
            """
        )
        assert rules_of(src, rules=["env-knob"]) == []

    def test_legacy_token_only_covers_migrated_rules(self):
        src = textwrap.dedent(
            """
            import asyncio
            import os

            async def f():
                await asyncio.gather(a(), b())  # async-hygiene: ok

            x = os.environ.get("AREAL_X")  # async-hygiene: ok
            """
        )
        fs = findings_of(src, rules=["bare-gather", "env-knob"])
        assert [f.rule for f in fs] == ["env-knob"]


# ------------------------------------------------------------------ #
# baseline semantics
# ------------------------------------------------------------------ #


class TestBaseline:
    SRC = textwrap.dedent(
        """
        import os

        a = os.environ.get("AREAL_A")
        b = os.environ.get("AREAL_B")
        """
    )

    def test_entry_suppresses_up_to_max_and_stale_entries_reported(self):
        fs = findings_of(self.SRC, path="areal_tpu/mod.py",
                         rules=["env-knob"])
        assert len(fs) == 2
        entries = [
            {"rule": "env-knob", "path": "areal_tpu/mod.py",
             "reason": "legacy knobs, migration tracked", "max": 2},
            {"rule": "env-knob", "path": "areal_tpu/gone.py",
             "reason": "was fixed — this entry is now stale"},
        ]
        remaining, stale = apply_baseline(fs, entries)
        assert remaining == []
        assert [e["path"] for e in stale] == ["areal_tpu/gone.py"]

    def test_default_max_is_one_finding(self):
        fs = findings_of(self.SRC, path="areal_tpu/mod.py",
                         rules=["env-knob"])
        entries = [{
            "rule": "env-knob", "path": "areal_tpu/mod.py",
            "reason": "one legacy knob",
        }]
        remaining, stale = apply_baseline(fs, entries)
        assert len(remaining) == 1 and stale == []

    def test_malformed_baseline_rejected(self):
        from tools.arealint import BaselineError, load_baseline

        import tempfile

        with tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False
        ) as f:
            json.dump({"entries": [{"rule": "env-knob",
                                    "path": "x.py"}]}, f)  # no reason
        with pytest.raises(BaselineError):
            load_baseline(f.name)
        os.unlink(f.name)


# ------------------------------------------------------------------ #
# the tree itself + CLI
# ------------------------------------------------------------------ #


class TestRepoIsClean:
    def test_rule_registry_has_the_required_families(self):
        migrated = {"bare-gather", "discarded-task",
                    "live-checkpoint-rmtree", "sleep-in-async"}
        jax_tpu = {"host-sync-in-hot-path", "retrace-hazard",
                   "donation-after-use", "env-knob",
                   "unregistered-counter", "unregistered-fault-point"}
        assert migrated <= set(RULES)
        assert jax_tpu <= set(RULES)
        assert len(RULES) >= 8

    # (the tree-clean gate itself is TestFullTreeGate below: one CLI run
    # covers areal_tpu/ tools/ tests/ with the baseline AND the runtime
    # budget — a second in-process scan of areal_tpu/ would just re-parse
    # the tree for ~14 s of tier-1 time)

    def test_baseline_has_no_hot_path_entries_for_train(self):
        """Acceptance: host-sync/donation findings in areal_tpu/train are
        FIXED or inline-annotated — never baselined away."""
        from tools.arealint import DEFAULT_BASELINE, load_baseline

        bl = os.path.join(REPO, DEFAULT_BASELINE)
        entries = load_baseline(bl) if os.path.exists(bl) else []
        offenders = [
            e for e in entries
            if e["rule"] in ("host-sync-in-hot-path", "donation-after-use")
            and e["path"].startswith("areal_tpu/train/")
        ]
        assert offenders == []


class TestCLI:
    def _run(self, *args, **kw):
        return subprocess.run(
            [sys.executable, "-m", "tools.arealint", *args],
            cwd=REPO, capture_output=True, text=True, timeout=120, **kw,
        )

    def test_json_scan_of_tree_exits_0(self):
        # base/ only: the full-tree error gate is the in-process
        # TestRepoIsClean scan; this checks the CLI+JSON plumbing without
        # paying for a second whole-tree parse
        r = self._run(
            os.path.join(REPO, "areal_tpu", "base"), "--format", "json"
        )
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["errors"] == 0
        assert {"findings", "stale_baseline", "warnings"} <= set(payload)

    def test_errors_exit_1(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import os\nx = os.environ.get('AREAL_X')\n"
        )
        r = self._run(str(bad), "--no-baseline")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "env-knob" in r.stdout

    def test_warn_only_exits_0(self, tmp_path):
        warn = tmp_path / "warn.py"
        warn.write_text(
            "import jax\ndef f(x):\n    return jax.jit(g)(x)\n"
        )
        r = self._run(str(warn), "--no-baseline")
        assert r.returncode == 0, r.stdout + r.stderr
        assert "retrace-hazard" in r.stdout

    def test_usage_errors_exit_2(self):
        assert self._run("--definitely-not-a-flag").returncode == 2
        r = self._run("--rules", "no-such-rule")
        assert r.returncode == 2
        assert "unknown rule" in r.stderr

    def test_list_rules(self):
        r = self._run("--list-rules")
        assert r.returncode == 0
        assert "host-sync-in-hot-path" in r.stdout


class TestSarif:
    """SARIF output is a determinism contract: the same findings render
    byte-identical SARIF everywhere (golden-file), and the CLI path
    round-trips through real findings."""

    GOLDEN = os.path.join(REPO, "tests", "data", "arealint_golden.sarif")

    def test_golden_file(self):
        from tools.arealint import Finding, sarif

        findings = [
            Finding(
                "areal_tpu/system/demo.py", 12, "bare-gather",
                "asyncio.gather(...) without return_exceptions=True",
                "error",
            ),
            Finding(
                "areal_tpu/train/demo.py", 40, "host-sync-cross-module",
                "jax.device_get(...) in helper() forces a host<->device "
                "sync on a hot path — reachable from hot root "
                "Engine.step()",
                "error",
            ),
            Finding(
                "tools/demo.py", 7, "jit-weak-type-drift",
                "jitted f() receives an int literal at position 0 here "
                "but a non-literal at another site",
                "warn",
            ),
            # one finding per v3 SPMD family (docs/static_analysis.md
            # "SPMD rules")
            Finding(
                "areal_tpu/train/demo.py", 12, "unknown-mesh-axis",
                "unknown mesh axis 'modle' in PartitionSpec — the mesh "
                "built by make_mesh has axes (data, fsdp, ctx, model)",
                "error",
            ),
            Finding(
                "areal_tpu/ops/demo.py", 21, "shard-map-spec-arity",
                "shard_map in_specs has 2 entries but body() takes 3 "
                "positional argument(s) — every operand needs exactly "
                "one spec",
                "error",
            ),
            Finding(
                "areal_tpu/gen/demo.py", 33, "hot-path-reshard",
                "with_sharding_constraint() changes the inferred "
                "sharding of 'x' from P(('data','fsdp')) to P() in "
                "decode() (reachable from hot root Engine.step()) — an "
                "implicit reshard on the hot path",
                "error",
            ),
            Finding(
                "areal_tpu/system/demo.py", 48,
                "host-divergence-collective",
                "branch in run() depends on host-local time.monotonic() "
                "but guards collective multihost.barrier() via "
                "save_recover_checkpoint()",
                "error",
            ),
            # one finding per v4 lifecycle rule (docs/static_analysis.md
            # "Lifecycle rules")
            Finding(
                "areal_tpu/gen/demo.py", 12, "leak-on-exception-path",
                "gen.kv-pages acquired by pool.alloc() is not released "
                "on every path out of admit() — release it in a finally "
                "/ context manager, or annotate the deliberate handoff "
                "with '# arealint: owns(gen.kv-pages, <reason>)'",
                "error",
            ),
            Finding(
                "areal_tpu/gen/demo.py", 55, "leak-on-cancellation",
                "this await can be cancelled while gen.kv-pages "
                "(acquired line 52 by pool.alloc()) is held — a "
                "CancelledError skips the release on line 57; wrap the "
                "window in try/finally (note: 'except Exception' does "
                "not catch CancelledError)",
                "error",
            ),
            Finding(
                "areal_tpu/gen/demo.py", 80, "double-release",
                "gen.kv-pages ('pages') is released again here — "
                "already released on line 78 with no re-acquire in "
                "between; the second release underflows the refcount "
                "(double free)",
                "error",
            ),
            Finding(
                "areal_tpu/gateway/demo.py", 31, "release-without-acquire",
                "gateway.token-bucket is released here on every path, "
                "but the matching acquire (line 24) happens only on "
                "some — the no-acquire path releases a resource it "
                "never held; guard the release with the same condition "
                "(or the handle's truthiness)",
                "error",
            ),
            Finding(
                "areal_tpu/gateway/demo.py", 24, "charge-refund-asymmetry",
                "gateway.token-bucket charged by bucket.try_acquire() "
                "is not released on every path out of submit() — refund "
                "it on every exit (try/finally), hand it to a callee "
                "that settles it, or annotate the deliberate handoff "
                "with '# arealint: owns(gateway.token-bucket, <reason>)'",
                "error",
            ),
            # one finding per v5 wire rule (docs/static_analysis.md
            # "Wire rules")
            Finding(
                "areal_tpu/gateway/demo.py", 18, "unknown-endpoint",
                "GenAPIClient.pause calls POST /pause, which no server "
                "module registers — the request can only 404",
                "error",
            ),
            Finding(
                "areal_tpu/system/demo.py", 61, "request-field-drift",
                "session.post posts /allocate_rollout without field "
                "'qid', which the handler "
                "(areal_tpu/system/gserver_manager.py:_allocate) reads "
                "unconditionally — guaranteed KeyError -> 500",
                "error",
            ),
            Finding(
                "areal_tpu/gateway/demo.py", 74, "response-field-drift",
                "GenAPIClient.metrics reads response key "
                "'slot_capacity' from /metrics_json, which no producer "
                "(areal_tpu/gen/server.py:_metrics) emits",
                "error",
            ),
            Finding(
                "areal_tpu/gen/demo.py", 92, "status-code-drift",
                "_generate emits HTTP 429 for POST /generate, but no "
                "caller branches on it or guards with raise_for_status "
                "— it surfaces as an unhandled exception",
                "warn",
            ),
            Finding(
                "areal_tpu/system/demo.py", 130, "retry-unbounded-status",
                "GenAPIClient.generate retries POST /generate on "
                "transient HTTP statuses, but the endpoint is "
                "non-idempotent — a timed-out request may still be "
                "running server-side and a re-send double-executes it "
                "(pass retry_connection_only=True)",
                "error",
            ),
        ]
        rendered = sarif.dumps(
            findings,
            root="/checkout",
            rule_ids=[
                "bare-gather", "host-sync-cross-module",
                "jit-weak-type-drift", "unknown-mesh-axis",
                "shard-map-spec-arity", "hot-path-reshard",
                "host-divergence-collective",
                "leak-on-exception-path", "leak-on-cancellation",
                "double-release", "release-without-acquire",
                "charge-refund-asymmetry",
                "unknown-endpoint", "request-field-drift",
                "response-field-drift", "status-code-drift",
                "retry-unbounded-status",
            ],
        ) + "\n"
        with open(self.GOLDEN, encoding="utf-8") as f:
            golden = f.read()
        assert rendered == golden, (
            "SARIF output drifted from tests/data/arealint_golden.sarif — "
            "if the change is deliberate (schema/rule-doc update), "
            "regenerate the golden file"
        )

    def test_cli_sarif_of_findings(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import os\nx = os.environ.get('AREAL_X')\n")
        r = subprocess.run(
            [sys.executable, "-m", "tools.arealint", str(bad),
             "--no-baseline", "--format", "sarif"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 1, r.stdout + r.stderr
        log = json.loads(r.stdout)
        assert log["version"] == "2.1.0"
        (run,) = log["runs"]
        assert run["tool"]["driver"]["name"] == "arealint"
        assert any(
            res["ruleId"] == "env-knob" and res["level"] == "error"
            for res in run["results"]
        )
        rule_ids = [ru["id"] for ru in run["tool"]["driver"]["rules"]]
        assert rule_ids == sorted(rule_ids)


class TestFullTreeGate:
    """Acceptance in one pass: the DEFAULT scan (areal_tpu/ tools/ tests/,
    parallel jobs, project rules on) exits 0 on this tree. The budget is
    the subprocess's own limit, with room for a machine that six test
    workers share (22.9 s in the PR 56 run of tier 1): a scan that hangs
    fails here, and no assertion reads the clock."""

    BUDGET_S = 360.0

    def test_default_tree_clean_and_under_budget(self):
        r = subprocess.run(
            [sys.executable, "-m", "tools.arealint"],
            cwd=REPO, capture_output=True, text=True,
            timeout=self.BUDGET_S,
        )
        # exit 0 == no error-severity findings; warn findings are
        # reported but non-fatal by policy (docs/static_analysis.md), so
        # the gate must NOT require a completely silent scan
        assert r.returncode == 0, r.stdout + r.stderr
