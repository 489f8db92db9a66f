"""chip_smoke.py has no CPU fallback, and the launcher hands each
chip-owning child its own chips.

The smoke itself only means something on the machine with the chip; what
can be pinned here is that it REFUSES to mean anything without one: held to
the CPU it exits non-zero and its last line says ``"ok": false`` with the
device JAX really found. The chip-assignment planner is pure (a layout and
a chip list in, per-child environments out), so its contract is pinned
here too.
"""

import json
import os
import subprocess
import sys

import pytest

from areal_tpu.apps import launcher
from areal_tpu.experiments import AsyncPPOExperiment, load_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0, r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # no phase after `device` ran
    phases = [json.loads(l).get("phase") for l in r.stdout.splitlines()[:-1]]
    assert phases == ["device", "failed"], phases


def _cfg(*overrides):
    return load_config(AsyncPPOExperiment, None, [
        'actor.arch={"n_layers": 1, "n_q_heads": 2, "n_kv_heads": 2,'
        ' "head_dim": 8, "hidden_dim": 16, "intermediate_dim": 32,'
        ' "vocab_size": 64}',
        *overrides,
    ])


def _chips(env):
    return [int(x) for x in env["TPU_VISIBLE_DEVICES"].split(",")]


@pytest.mark.parametrize(
    "overrides,chip_ids,want",
    [
        pytest.param(
            ["gen.tp_size=2", "actor.parallel=d1f2m1"], [0, 1, 2, 3],
            {"gen_server/0": [0, 1], "trainer": [2, 3]},
            id="tp2-server+2chip-trainer-on-4",
        ),
        pytest.param(
            ["gen.n_servers=3"], [0, 1, 2, 3],
            {"gen_server/0": [0], "gen_server/1": [1], "gen_server/2": [2],
             "trainer": [3]},
            id="3-servers+trainer-on-4",
        ),
        pytest.param(
            ["gen.n_servers=2", "trainer_device=cpu"], [4, 5, 6, 7],
            {"gen_server/0": [4], "gen_server/1": [5]},
            id="restricted-parent-keeps-its-own-ids",
        ),
    ],
)
def test_plan_gives_children_disjoint_chips(overrides, chip_ids, want):
    cfg = _cfg(*overrides)
    plan = launcher.plan_chips(launcher.chip_owners(cfg), chip_ids)
    got = {name: _chips(env) for name, env in plan.items()}
    assert got == want
    flat = [c for ids in got.values() for c in ids]
    assert len(flat) == len(set(flat))          # disjoint
    for name, env in plan.items():
        # one process per block, a block shape matching its chip count,
        # and a mesh-controller port no sibling shares
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        n = 1
        for x in env["TPU_CHIPS_PER_PROCESS_BOUNDS"].split(","):
            n *= int(x)
        assert n == len(got[name])
    for key in ("TPU_MESH_CONTROLLER_PORT", "TPU_PROCESS_PORT"):
        ports = [env[key] for env in plan.values()]
        assert len(ports) == len(set(ports)), key
    for env in plan.values():
        assert env["TPU_PROCESS_ADDRESSES"].endswith(env["TPU_PROCESS_PORT"])


def test_plan_leaves_a_whole_host_owner_unrestricted():
    cfg = _cfg("gen.device=cpu", "actor.parallel=d1f2m2")
    plan = launcher.plan_chips(launcher.chip_owners(cfg), [0, 1, 2, 3])
    assert plan == {"trainer": {}}


@pytest.mark.parametrize(
    "overrides,chip_ids",
    [
        # the one-chip host: a gen server and a trainer both on the default
        # device is one process too many
        pytest.param([], [0], id="async-ppo-on-one-chip"),
        pytest.param(["gen.tp_size=2", "actor.parallel=d1f2m2"],
                     [0, 1, 2, 3], id="6-chips-on-4"),
        pytest.param(["evaluator.enabled=true", "evaluator.device=",
                      "gen.tp_size=2", "actor.parallel=d1f2m1"],
                     [0, 1, 2, 3], id="tpu-evaluator-is-an-owner-too"),
    ],
)
def test_oversubscribed_layout_raises_at_launch(overrides, chip_ids):
    cfg = _cfg(*overrides)
    with pytest.raises(ValueError, match="chips"):
        launcher.plan_chips(launcher.chip_owners(cfg), chip_ids)


def test_cpu_run_plans_nothing(monkeypatch):
    """Held to the CPU the host offers no chips (virtual CPU devices are
    per-process): nothing to hand out, nothing to oversubscribe."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launcher.local_chip_ids() == []
    assert launcher.plan_chips(launcher.chip_owners(_cfg()), []) == {}


def test_parent_visibility_bounds_the_host(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setenv("TPU_VISIBLE_DEVICES", "2,3")
    assert launcher.local_chip_ids() == [2, 3]


def test_child_env_is_restored_after_spawn(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    with launcher._child_env({"TPU_VISIBLE_DEVICES": "1", "JAX_PLATFORMS": ""}):
        assert os.environ["TPU_VISIBLE_DEVICES"] == "1"
        assert os.environ["JAX_PLATFORMS"] == ""
    assert "TPU_VISIBLE_DEVICES" not in os.environ
    assert os.environ["JAX_PLATFORMS"] == "cpu"


def test_compile_cache_has_one_place(
        monkeypatch, tmp_path, no_persistent_cache):
    """Set from outside, the variable stands and nothing else is touched;
    unset on an accelerator run, the one fixed path in the checkout is
    exported so children and a later ``import jax`` agree on it. (Outside
    the test run's own cache, whose variable it sets and unsets; the
    monkeypatch puts the run's directory back.)"""
    from areal_tpu.base import compile_cache, constants

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.configure() is None     # CPU runs cache nothing
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ

    import jax

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_PLATFORMS", "")
    try:
        fixed = compile_cache.configure()
        assert fixed == os.path.join(ROOT, ".jax_compile_cache")
        assert fixed == constants.compile_cache_dir()
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


def test_launcher_parent_never_initialises_a_backend(tmp_path):
    """run_async_ppo's parent calls _setup_worker_env and then only spawns:
    a parent that initialised a JAX backend would hold the chips its
    children need."""
    code = (
        "from areal_tpu.apps import launcher\n"
        "from areal_tpu.experiments import AsyncPPOExperiment, load_config\n"
        "from areal_tpu.system import worker_base\n"
        f"cfg = load_config(AsyncPPOExperiment, None, ['fileroot={tmp_path}'])\n"
        "launcher._setup_worker_env(cfg, '')\n"
        "worker_base.mark_experiment_running(cfg.experiment_name, cfg.trial_name)\n"
        "launcher.plan_chips(launcher.chip_owners(cfg), launcher.local_chip_ids())\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
