"""The program store (``areal_tpu/base/program_store.py``, ISSUE 61): a
program built in one process is LOADED in the next, bit for bit and with
no trace booked; the key misses when anything the program was built from
changes; a bad file is a miss and is written again; no cache, no store;
the engines' books (``n_jit_entries``) read the same with it on and off;
it holds itself under the cache's bound, and JAX's own sweep of the cache
directory leaves it alone. The session runs with the store OFF (nothing
here configures a cache in the pytest process): every case opens one of
its own."""

import ast
import dataclasses
import inspect
import json
import os
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.base import compile_cache, program_store, tracing
from areal_tpu.base import metrics as metrics_mod
from benchmark.run import ROOT, load_reader

READER = "start.store_hit_share"


@pytest.fixture
def store(tmp_path, no_persistent_cache):
    """A store of the test's own, closed again afterwards. Every program
    is compiled HERE: an executable that the session's compile cache hands
    over is not stored on the CPU (``program_store._reserialises``)."""
    was = program_store.directory()
    path = program_store.open_in(str(tmp_path / "cache"))
    yield path
    program_store.flush()
    program_store.open_in(None)
    program_store._dir = was


@pytest.fixture
def counters(monkeypatch):
    reg = metrics_mod.CounterRegistry()
    monkeypatch.setattr(metrics_mod, "counters", reg)
    tracing.drain()
    yield reg
    tracing.drain()


def _hits_misses(reg):
    return (reg.get("compile/store_hits"), reg.get("compile/store_misses"))


# --------------------------------------------------------------------- #
# Two processes
# --------------------------------------------------------------------- #

_CHILD = r"""
import hashlib, json, os, sys
sys.path.insert(0, {root!r}); sys.path.insert(0, os.path.join({root!r}, "tests"))
from areal_tpu.base import compile_cache, metrics, program_store, tracing
assert compile_cache.configure() == os.environ["JAX_COMPILATION_CACHE_DIR"]
import jax, jax.numpy as jnp, numpy as np

def chunk_like():
    # a decode chunk in small: a scan over steps, the state donated,
    # flags beside it
    def chunk(w, state, table):
        def body(s, _):
            h = jnp.tanh(s["h"] @ w + table.sum())
            return dict(h=h, n=s["n"] + 1), h.max()
        state, tops = jax.lax.scan(body, state, None, length=4)
        return state, (tops.sum(), state["n"])
    f = program_store.stored_jit(
        chunk, name="test/chunk", key=(4,), built_from=("cfg", 1),
        donate_argnums=(1,))
    w = jnp.linspace(-1, 1, 64, dtype=jnp.float32).reshape(8, 8)
    state = dict(h=jnp.ones((3, 8), jnp.float32), n=jnp.zeros((), jnp.int32))
    for _ in range(3):
        state, flags = f(w, state, np.arange(6, dtype=np.int32))
    return "jit(chunk)", [state["h"], flags[0], flags[1]], f._cache_size()

def _trainer():
    import test_train_engine as T
    from areal_tpu.api.data import MicroBatchSpec
    eng = T.TrainEngine(T.TINY, optimizer=T.OptimizerConfig(lr=1e-3))
    eng.init_random(0); eng.setup_optimizer(total_train_steps=50)
    sample = T._make_sample(np.random.default_rng(42), n_items=8, with_reward=True)
    return eng, sample, MicroBatchSpec(n_mbs=2, max_tokens_per_mb=64)

def train_step():
    from areal_tpu.interfaces.sft import sft_loss_fn
    eng, sample, spec = _trainer()
    losses = [eng.train_batch(sample, spec, sft_loss_fn)["loss"] for _ in range(3)]
    return ("jit(train_step)", jax.tree.leaves(eng.params) + [np.float64(l) for l in losses],
            eng.n_jit_entries())

def actor_step():
    # the PPO actor's loss CLOSES over its hyper-parameters: eps_clip is a
    # constant of the program, seen by no argument
    from areal_tpu.api.model import PPOHyperparameters
    from areal_tpu.interfaces.ppo import PPOActorInterface
    eng, sample, spec = _trainer()
    n = sample.data["packed_input_ids"].shape[0]
    sample.update_(type(sample)(
        keys={{"packed_logprobs", "seq_no_eos_mask"}}, ids=list(sample.ids),
        seqlens={{"packed_logprobs": sample.seqlens["packed_input_ids"],
                 "seq_no_eos_mask": [[1]] * len(sample.ids)}},
        # about the fresh model's own (uniform over 128), 0.3 nats off: some
        # ratios are clipped at 0.2 and not at 0.3
        data={{"packed_logprobs": np.random.default_rng(7).normal(
                  -np.log(128), 0.3, n).astype(np.float32),
              "seq_no_eos_mask": np.zeros(len(sample.ids), bool)}}))
    actor = PPOActorInterface(hp=PPOHyperparameters(
        disable_value=True, ppo_n_minibatches=1, use_decoupled_loss=False,
        recompute_logprob=False, eps_clip=float(sys.argv[2])))
    # (the schedule's first rate is 0: the step's statistics tell, not the
    # weights)
    stats = jax.device_get(actor.train_step(eng, sample, spec))
    return ("jit(train_step)", [np.float64(stats[k]) for k in sorted(stats)],
            eng.n_jit_entries())

name, outs, n_entries = {{"chunk": chunk_like, "train_step": train_step,
                         "actor_step": actor_step}}[sys.argv[1]]()
program_store.flush()
recs = [s["attrs"] for s in tracing.spans_since(0.0)
        if s["name"] == "compile/program" and s["attrs"]["fun_name"] == name]
print("RESULT " + json.dumps({{
    "digest": hashlib.sha256(b"".join(np.asarray(x).tobytes() for x in outs)).hexdigest(),
    "n_entries": n_entries, "records": recs,
    "counters": {{k: v for k, v in metrics.counters.snapshot().items()
                 if k.startswith("compile/")}},
}}))
"""


def _child(program, cache_dir, *more):
    p = subprocess.run(
        [sys.executable, "-c", _CHILD.format(root=ROOT), program, *more],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
             # XLA:CPU prints its machine's features at every load
             "TF_CPP_MIN_LOG_LEVEL": "3"},
    )
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    return json.loads(next(
        l for l in p.stdout.splitlines() if l.startswith("RESULT "))[7:])


@pytest.mark.parametrize("program", ["chunk", "train_step"])
def test_built_in_one_process_loaded_in_the_next(program, tmp_path):
    cold = _child(program, tmp_path / "cache")
    warm = _child(program, tmp_path / "cache")
    assert warm["digest"] == cold["digest"]         # bit for bit
    assert warm["n_entries"] == cold["n_entries"] == 1
    assert cold["counters"].get("compile/store_hits", 0) == 0
    assert cold["counters"]["compile/store_misses"] >= 1
    (built,), (loaded,) = cold["records"], warm["records"]
    assert built["stored"] is False and built["trace_s"] > 0
    assert loaded["stored"] is True and loaded["cache_hit"] is True
    assert loaded["trace_s"] == 0 and loaded["lower_s"] == 0
    assert loaded["backend_s"] > 0
    assert set(built) == set(loaded)    # every attribute the readers index
    # the engine's one program, and nothing of the engine's was traced
    hits = warm["counters"]["compile/store_hits"]
    assert hits >= 1 and warm["counters"].get("compile/store_misses", 0) == 0
    assert warm["counters"]["compile/programs"] == cold["counters"]["compile/programs"]


def test_a_restart_with_another_hyperparameter_builds_its_own_program(tmp_path):
    """A trainer restarted with another ``eps_clip``, same model, optimizer
    and shapes, must not load the step built with the old one; restarted
    with the old one it does."""
    first = _child("actor_step", tmp_path / "cache", "0.2")
    other = _child("actor_step", tmp_path / "cache", "0.3")
    again = _child("actor_step", tmp_path / "cache", "0.2")
    (built,), (rebuilt,), (loaded,) = (
        r["records"] for r in (first, other, again))
    assert built["stored"] is False and rebuilt["stored"] is False
    assert other["counters"].get("compile/store_hits", 0) == 0
    assert other["digest"] != first["digest"]   # eps_clip is in the program
    assert loaded["stored"] is True and again["digest"] == first["digest"]
    assert again["counters"].get("compile/store_misses", 0) == 0


# --------------------------------------------------------------------- #
# The key
# --------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class _Cfg:
    n_layers: int = 2
    eps: float = 1e-6


def _program(cfg=_Cfg(), static=(4, 32), scale=2.0):
    def step(x, y):
        return x * scale + y.sum() + cfg.eps * cfg.n_layers

    return program_store.stored_jit(
        step, name="test/step", key=static, built_from=(cfg, scale))


_X = np.ones((3, 5), np.float32)
_Y = np.ones((2,), np.float32)
CHANGES = {
    "a config field": dict(make=dict(cfg=_Cfg(eps=1e-5))),
    "the static key": dict(make=dict(static=(4, 64))),
    "an engine setting": dict(make=dict(scale=3.0)),
    "an argument's shape": dict(args=(np.ones((3, 6), np.float32), _Y)),
    "an argument's dtype": dict(args=(_X, np.ones((2,), np.int32))),
    "the source digest": dict(patch="source"),
    "a program-shaping flag": dict(env=("AREAL_FLASH_BWD_PIPELINE", "0")),
    "a flag of the package's that nobody listed": dict(
        env=("AREAL_A_LATER_PRS_FLAG", "1")),
    "the compiler's flags": dict(env=("LIBTPU_INIT_ARGS", "--xla_tpu_x=1")),
    "a jax setting made in code": dict(
        config=("jax_default_matmul_precision", "highest")),
}


@pytest.mark.parametrize("what", list(CHANGES))
def test_key_misses_when_one_thing_it_was_built_from_changes(
        what, store, counters, monkeypatch):
    change = CHANGES[what]
    want = _program()(_X, _Y)
    program_store.flush()
    assert _hits_misses(counters) == (0, 1)
    # the same again, a new wrapper as a new process would make: a hit
    np.testing.assert_array_equal(_program()(_X, _Y), want)
    assert _hits_misses(counters) == (1, 1)
    if change.get("patch") == "source":
        monkeypatch.setattr(program_store, "source_digest", lambda: "other")
    if "env" in change:
        monkeypatch.setenv(*change["env"])
    if "config" in change:
        was = getattr(jax.config, change["config"][0])
        jax.config.update(*change["config"])
    try:
        changed = _program(**change.get("make", {}))
        changed(*change.get("args", (_X, _Y)))
    finally:
        if "config" in change:
            jax.config.update(change["config"][0], was)
    assert _hits_misses(counters) == (1, 2), what
    assert changed._cache_size() == 1


def test_a_restarted_worker_still_finds_its_programs(
        store, counters, monkeypatch):
    """Where files live, who talks to whom and what is logged, traced or
    watched shapes no program: a worker restarted under another trial's
    directory, port or trace settings, or a ``--trace 1`` run after a
    ``--trace 0`` one, hits."""
    want = _program()(_X, _Y)
    program_store.flush()
    for name, value in [
            ("AREAL_FILEROOT", "/tmp/another"), ("AREAL_TRACE_SPANS", "1"),
            ("AREAL_DUMP_TRACE", "1"), ("AREAL_GW_HEDGE", "0"),
            ("AREAL_GATEWAY_PORT", "8123"), ("AREAL_WATCHDOG_TIMEOUT_S", "9"),
            ("AREAL_COORDINATOR", "localhost:1234"), ("BENCH_RUN", "7"),
            ("JAX_COMPILATION_CACHE_MAX_SIZE", "1000000")]:
        monkeypatch.setenv(name, value)
    np.testing.assert_array_equal(_program()(_X, _Y), want)
    assert _hits_misses(counters) == (1, 1)


@pytest.mark.parametrize("why", [
    "no stable text", "a function from elsewhere", "the process dumps its IR"])
def test_what_cannot_be_keyed_stays_on_jit(why, store, counters, monkeypatch):
    """A closure over an object without a stable text cannot be keyed, nor
    can a function whose text no digest holds (this module's), and a
    process under ``JAX_DUMP_IR_TO`` (``chip_smoke.py``'s children) wants
    every program lowered: the program runs as ``jax.jit``'s, with jit's
    own count of specialisations, and the store is never asked."""
    built_from = {"no stable text": object(),
                  "a function from elsewhere": lambda x: x}.get(why, ())
    if why == "the process dumps its IR":
        monkeypatch.setenv("JAX_DUMP_IR_TO", str(store) + "-ir")
    f = program_store.stored_jit(
        lambda x: x + 1, name="test/opaque", built_from=built_from)
    np.testing.assert_array_equal(f(_X), _X + 1)
    np.testing.assert_array_equal(f(_Y), _Y + 1)
    assert _hits_misses(counters) == (0, 0)
    assert f._cache_size() == 2
    with pytest.raises(program_store.Unkeyable):
        program_store.fingerprint(types.SimpleNamespace(x=object()))


def _loss_of(what, **hp):
    from areal_tpu.api.model import PPOHyperparameters
    from areal_tpu.interfaces import ppo, reward

    if what == "actor":
        return ppo.PPOActorInterface(
            hp=PPOHyperparameters(**hp))._actor_loss_fn
    if what == "critic":
        return ppo.PPOCriticInterface(
            hp=PPOHyperparameters(**hp))._critic_loss_fn
    return reward.PairedRewardInterface(**hp)._rw_loss_fn


@pytest.mark.parametrize("what, field, a, b", [
    ("actor", "eps_clip", 0.2, 0.3), ("actor", "c_clip", None, 3.0),
    ("actor", "behav_imp_weight_cap", None, 5.0),
    ("actor", "use_decoupled_loss", True, False),
    ("critic", "value_eps_clip", 0.2, 0.3),
    ("reward", "max_pairs_per_prompt", 8, 4)])
def test_a_loss_function_is_keyed_by_what_its_closure_holds(what, field, a, b):
    """The trainer's loss functions are closures of THIS package: their
    text is in the source digest, the hyper-parameters they bake into the
    program are in no argument. The key holds them."""
    fp = program_store.fingerprint
    assert fp(_loss_of(what, **{field: a})) == fp(_loss_of(what, **{field: a}))
    assert fp(_loss_of(what, **{field: a})) != fp(_loss_of(what, **{field: b}))


def test_an_optimizer_is_keyed_by_what_its_updates_hold():
    import optax

    fp = program_store.fingerprint
    assert fp(optax.sgd(1.0)) == fp(optax.sgd(1.0))
    assert fp(optax.sgd(1.0)) != fp(optax.sgd(0.5))
    assert fp(optax.adamw(optax.schedules.linear_schedule(0.0, 1e-3, 10))) != fp(
        optax.adamw(optax.schedules.linear_schedule(0.0, 1e-3, 20)))
    with pytest.raises(program_store.Unkeyable):    # this module's: no digest
        fp(optax.adamw(lambda step: 1e-3))


def _self_reads(fn):
    return {n.attr for n in ast.walk(fn)
            if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name)
            and n.value.id == "self"}


def _methods(module, cls):
    tree = ast.parse(inspect.getsource(module))
    (node,) = [n for n in tree.body
               if isinstance(n, ast.ClassDef) and n.name == cls]
    return {f.name: f for f in node.body if isinstance(f, ast.FunctionDef)}


def _read_by(methods, start, stop=()):
    """Every ``self.x`` that ``start`` reads, itself or through a method of
    the class it reaches, ``stop`` left out: ``{x: [methods]}``."""
    todo, seen, found = [start], set(stop), {}
    while todo:
        m = todo.pop()
        if m in seen:
            continue
        seen.add(m)
        for attr in _self_reads(methods[m]):
            if attr in methods:
                todo.append(attr)
            else:
                found.setdefault(attr, []).append(m)
    return found


# what a builder may read of its engine WITHOUT its being in the key
# (beside what ``_built_from`` / ``_stored_jit`` name), and why
NOT_SETTINGS = {
    "gen": {
        "_jit_chunk", "_jit_extend", "_jit_kv_write", "_jit_commit",
        "_jit_state",               # the dictionaries the programs live in
        "_repl", "_state_sh",       # shardings over the mesh: jit options
        "params", "state",          # arguments: the signature holds them
        "_pull_block",              # handed over as the static key
    },
    "train": {
        "_jit_cache", "_param_shardings",   # as above
        "opt_state",                # an argument; its shardings an option
    },
}


def test_every_setting_a_builder_reads_is_in_the_key():
    """``_built_from`` and ``_stored_jit`` are lists kept by hand: a later
    change that reads a new ``self.x`` inside a traced closure, or in a
    method a builder calls, fails here until the key names it (or
    ``NOT_SETTINGS`` says why it shapes no program)."""
    from areal_tpu.gen import engine as gen
    from areal_tpu.train import engine as train

    methods = _methods(gen, "GenerationEngine")
    keyed = _self_reads(methods["_built_from"])
    builders = {
        name: any(k.arg == "built_from" for k in call.keywords)
        for name, f in methods.items() for call in ast.walk(f)
        if isinstance(call, ast.Call)
        and getattr(call.func, "attr", None) == "stored_jit"}
    assert sum(builders.values()) == 3 and len(builders) == 7, builders
    for name, has_key in builders.items():
        # ``_jit_sharding``'s result is given to jit: the options' part
        read = _read_by(methods, name, stop=("_built_from", "_jit_sharding"))
        extra = set(read) - NOT_SETTINGS["gen"] - (keyed if has_key else set())
        assert not extra, f"{name} reads {sorted(extra)}: not in the key"

    methods = _methods(train, "TrainEngine")
    read = _read_by(methods, "_get_jitted", stop=("_stored_jit",))
    keyed = _self_reads(methods["_stored_jit"])
    extra = set(read) - NOT_SETTINGS["train"] - keyed
    assert not extra, f"_get_jitted reads {sorted(extra)}: not in the key"


# --------------------------------------------------------------------- #
# Files
# --------------------------------------------------------------------- #


def _entries(store):
    return sorted(
        f for f in os.listdir(store) if f.endswith(program_store.SUFFIX))


@pytest.mark.parametrize("damage", ["truncated", "foreign", "of another key"])
def test_a_bad_file_is_a_miss_and_is_written_again(damage, store, counters):
    want = _program()(_X, _Y)
    program_store.flush()
    (name,) = _entries(store)
    path = os.path.join(store, name)
    good = open(path, "rb").read()
    if damage == "truncated":
        open(path, "wb").write(good[: len(good) // 2])
    elif damage == "foreign":
        open(path, "wb").write(b"not a program at all")
    else:
        other = _program(static=(9, 9))
        other(_X, _Y)
        program_store.flush()
        (theirs,) = set(_entries(store)) - {name}
        os.replace(os.path.join(store, theirs), path)
    before = _hits_misses(counters)
    np.testing.assert_array_equal(_program()(_X, _Y), want)
    program_store.flush()
    assert _hits_misses(counters) == (before[0], before[1] + 1)
    assert open(path, "rb").read()[:16] == good[:16]    # written again
    np.testing.assert_array_equal(_program()(_X, _Y), want)
    assert _hits_misses(counters) == (before[0] + 1, before[1] + 1)


def test_no_cache_no_store(monkeypatch):
    """Where ``configure()`` returns None (held to the CPU, no cache asked
    for) the store is off and ``stored_jit`` is ``jax.jit``."""
    was = program_store.directory()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    try:
        assert compile_cache.configure() is None
        assert program_store.directory() is None
        f = _program()
        assert not isinstance(f, program_store.StoredProgram)
        assert type(f) is type(jax.jit(lambda x: x))
    finally:
        program_store._dir = was


def test_configure_opens_the_store_inside_the_cache(monkeypatch, tmp_path):
    was = program_store.directory()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.configure() == str(tmp_path)
        assert program_store.directory() == str(tmp_path / "programs")
        assert isinstance(_program(), program_store.StoredProgram)
    finally:
        program_store._dir = was


@pytest.fixture
def bound():
    was = jax.config.jax_compilation_cache_max_size

    def set_(n):
        jax.config.update("jax_compilation_cache_max_size", n)

    yield set_
    jax.config.update("jax_compilation_cache_max_size", was)


def test_the_store_evicts_past_its_bound(store, counters, bound):
    """Under ``JAX_COMPILATION_CACHE_MAX_SIZE``, least recently USED out."""
    sizes = {}
    for i in range(4):
        _program(static=(i,))(_X, _Y)
        program_store.flush()
        (new,) = set(_entries(store)) - set(sizes)
        sizes[new] = os.path.getsize(os.path.join(store, new))
        os.utime(os.path.join(store, new), (i, i))      # oldest first
    first, second = list(sizes)[:2]
    _program(static=(0,))(_X, _Y)       # a hit marks the first used
    assert _hits_misses(counters)[0] == 1
    bound(sum(sizes.values()) - 1)
    _program(static=(4,))(_X, _Y)
    program_store.flush()
    left = _entries(store)
    assert second not in left and first in left
    assert sum(os.path.getsize(os.path.join(store, f)) for f in left) <= (
        sum(sizes.values()) - 1)
    bound(0)        # a cache that may hold nothing: nothing is written
    _program(static=(5,))(_X, _Y)
    program_store.flush()
    assert len(_entries(store)) == len(left)


def test_jaxs_sweep_of_the_cache_leaves_the_store_alone(store):
    """The store lives INSIDE the compile cache's directory, which JAX's
    least-recently-used sweep owns: the sweep takes ``*-cache`` files of
    the top level only."""
    from jax._src.lru_cache import LRUCache

    _program()(_X, _Y)
    program_store.flush()
    mine = _entries(store)
    cache = LRUCache(os.path.dirname(store), max_size=3000)
    for i in range(8):
        cache.put(f"k{i}", bytes(1000))
    assert len([f for f in os.listdir(os.path.dirname(store))
                if f.endswith("-cache")]) == 3      # it swept
    assert _entries(store) == mine
    np.testing.assert_array_equal(
        _program()(_X, _Y), _X * 2.0 + 2.0 + 2e-6)


# --------------------------------------------------------------------- #
# The books
# --------------------------------------------------------------------- #


def _gen_entries():
    from areal_tpu.gen.engine import GenerationEngine, GenRequest
    from areal_tpu.models import transformer as tfm
    from tests import test_gen_engine as dense

    eng = GenerationEngine(
        dense.CFG, tfm.init_params(dense.CFG, jax.random.key(5)),
        max_slots=4, max_seqlen=64, max_new_tokens_cap=16, page_size=8,
        admit_buckets=(1, 2), seed=3)
    for i in range(3):
        eng.submit(GenRequest(
            rid=f"r{i}", input_ids=list(range(1, 12 + i)), temperature=0.0,
            max_new_tokens=6 + i))
    outs = eng.run_until_done(decode_steps=4)
    return eng.n_jit_entries(), eng.program_sizes(), sorted(
        (o.rid, tuple(o.output_ids)) for o in outs)


def _train_entries():
    from areal_tpu.api.data import MicroBatchSpec
    from areal_tpu.interfaces.sft import sft_loss_fn
    from tests import test_train_engine as T

    eng = T.TrainEngine(T.TINY, optimizer=T.OptimizerConfig(lr=1e-3))
    eng.init_random(0)
    eng.setup_optimizer(total_train_steps=50)
    rng = np.random.default_rng(42)
    spec = MicroBatchSpec(n_mbs=2, max_tokens_per_mb=64)
    sample = T._make_sample(rng, n_items=8)
    losses = [eng.train_batch(sample, spec, sft_loss_fn)["loss"]
              for _ in range(2)]
    losses.append(eng.eval_batch(sample, spec, sft_loss_fn)["loss"])
    return eng.n_jit_entries(), None, losses


@pytest.mark.parametrize("engine", ["gen", "train"])
def test_n_jit_entries_reads_the_same_with_the_store_on_and_off(
        engine, tmp_path, counters, no_persistent_cache):
    run = {"gen": _gen_entries, "train": _train_entries}[engine]
    assert program_store.directory() is None    # the session's state
    off = run()
    was = program_store.open_in(str(tmp_path / "cache"))
    try:
        cold = run()
        program_store.flush()
        warm = run()
    finally:
        program_store.flush()
        program_store.open_in(None)
    assert off == cold == warm
    assert off[0] > 0
    hits, misses = _hits_misses(counters)
    assert hits > 0 and misses > 0 and was is not None


# --------------------------------------------------------------------- #
# The benchmark's reader
# --------------------------------------------------------------------- #


def _bench(t_open):
    return types.SimpleNamespace(t_open=t_open)


@pytest.mark.parametrize("hits, misses, want", [
    (45, 5, 90.0), (0, 33, 0.0), (16, 0, 100.0), (0, 0, None)])
def test_reader_reads_the_share_of_the_start(counters, hits, misses, want):
    counters.add("compile/programs", 50)    # a program with a listener
    if hits:
        counters.add("compile/store_hits", hits)
    if misses:
        counters.add("compile/store_misses", misses)
    assert load_reader(READER).read(_bench(time.perf_counter())) == want


def test_reader_leaves_out_what_was_asked_for_after_the_window_opened(
        store, counters):
    tracing.listen_for_compiles()
    _program()(_X, _Y)                          # a miss of the start
    program_store.flush()
    _program()(_X, _Y)                          # a hit of the start
    t_open = time.perf_counter()
    _program()(_X, _Y)                          # a hit after it
    _program(static=(7, 7))(_X, _Y)             # a miss after it
    assert _hits_misses(counters) == (2, 2)
    assert load_reader(READER).read(_bench(t_open)) == 50.0


def test_reader_finds_nothing_in_a_program_without_a_store(counters):
    """Laid over the parent commit it returns None and does not raise."""
    counters.add("compile/programs", 30)
    counters.add("compile/cache_hits", 30)
    assert load_reader(READER).read(_bench(0.0)) is None
