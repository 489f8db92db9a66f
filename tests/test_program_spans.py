"""What the benchmark's per-layer readers stand on (ISSUE 24): every
``pallas_call`` carries its kernel's name, and ``benchmark/program_spans``
attributes device idle to the program's spans on synthetic traces with
known answers."""

import functools
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import program_spans

MS = 1e6    # ns


# ---- kernel names, from the jaxpr (no TPU needed) -------------------- #

def _pallas_names(fn, *args):
    """Names of every pallas_call in ``fn``'s jaxpr, nested ones included."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                p = eqn.params      # the key moved between jax releases
                names.append(
                    p["name"] if "name" in p else p["name_and_src_info"].name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _flash_args(T=256, hq=4, hkv=2, d=8):
    k = jax.random.split(jax.random.key(0), 3)
    return (
        jax.random.normal(k[0], (T, hq, d)), jax.random.normal(k[1], (T, hkv, d)),
        jax.random.normal(k[2], (T, hkv, d)), jnp.ones((T,), jnp.int32),
    )


def _flash(max_seqlen, grad):
    from areal_tpu.ops.pallas import flash_attention as fa

    attn = functools.partial(
        fa.packed_flash_attention, softmax_scale=8 ** -0.5, block_size=128,
        max_seqlen=max_seqlen)
    if not grad:
        return attn
    return jax.grad(
        lambda q, k, v, seg: jnp.sum(attn(q, k, v, seg)), argnums=(0, 1, 2))


def _paged(int8):
    from areal_tpu.ops.pallas import paged_attention as pp

    B, hq, hkv, d, L, P, M, page = 8, 4, 2, 8, 2, 16, 4, 128 if int8 else 8
    args = [
        jnp.zeros((B, hq, d)), jnp.zeros((B, hkv, d)), jnp.zeros((B, hkv, d)),
        jnp.zeros((L, P, 2, hkv, page, d), jnp.int8 if int8 else jnp.float32),
        jnp.int32(0), jnp.zeros((B, M), jnp.int32), jnp.ones((B,), jnp.int32),
    ]
    if int8:
        return (lambda *a: pp.decode(*a[:7], scales=a[7])), args + [
            jnp.ones((L, P, 2, hkv, page), jnp.float32)]
    return pp.decode, args


def _fused_sample():
    from areal_tpu.ops.pallas import fused_sample as fs

    R, E, V = 8, 32, 256
    return (lambda x, w: fs.fused_sample_pallas(
        jax.random.key(0), x, w, jnp.ones((R,)), jnp.zeros((R,), bool),
    )), [jnp.zeros((R, E)), jnp.zeros((E, V))]


KERNELS = [
    # one forward and one fused backward since PR 58: with a static
    # ``max_seqlen`` or without, both walk the pair list
    pytest.param(lambda: (_flash(128, False), _flash_args()),
                 {"flash_fwd"}, id="flash_fwd"),
    pytest.param(lambda: (_flash(None, False), _flash_args()),
                 {"flash_fwd"}, id="flash_fwd_no_max_seqlen"),
    pytest.param(lambda: (_flash(128, True), _flash_args()),
                 {"flash_fwd", "flash_bwd_fused"}, id="flash_bwd_fused"),
    pytest.param(lambda: (_flash(None, True), _flash_args()),
                 {"flash_fwd", "flash_bwd_fused"},
                 id="flash_bwd_fused_no_max_seqlen"),
    pytest.param(lambda: (_flash(128, True), _flash_args()),
                 {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}, id="flash_bwd_split"),
    pytest.param(lambda: _paged(False), {"paged_decode"}, id="paged_decode"),
    pytest.param(lambda: _paged(True), {"paged_decode_int8"}, id="paged_decode_int8"),
    pytest.param(_fused_sample, {"fused_sample"}, id="fused_sample"),
]


@pytest.mark.parametrize("make,want", KERNELS)
def test_every_pallas_call_is_named(make, want, monkeypatch, request):
    if "split" in request.node.callspec.id:
        # the separate dq / dkv sweeps are what a context too long for the
        # fused backward's whole-T scratch falls back to
        from areal_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "FUSED_BWD_MAX_DQ_BYTES", 0)
    fn, args = make()
    assert set(_pallas_names(fn, *args)) == want


# ---- idle attribution on synthetic intervals -------------------------- #

def _loaded(ops, spans, window=(0.0, 100 * MS)):
    return {
        "raw": {
            "planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": ops},
                {"name": "XLA Modules", "events": []}]}],
            "host_spans": [
                ["bench/trace_window", window[0], window[1] - window[0]]],
        },
        "spans": spans,
    }


# device busy [10,40) (a while covering its body) and [60,70): idle is
# [0,10) [40,60) [70,100) = 60 ms of a 100 ms window
OPS = [["while", 10 * MS, 30 * MS], ["body", 12 * MS, 20 * MS],
       ["copy", 60 * MS, 10 * MS]]


@pytest.mark.parametrize("spans,names,want_ms", [
    # nested spans of the same names are a union, not a sum
    ([["a/outer", 35 * MS, 30 * MS], ["a/inner", 45 * MS, 10 * MS]],
     ["a/outer", "a/inner"], 20.0),
    # a gap that straddles a span's edge counts only its part inside
    ([["a/edge", 50 * MS, 25 * MS]], ["a/edge"], 15.0),
    # spans on two threads that overlap in time
    ([["a/t1", 0.0, 8 * MS], ["a/t2", 5 * MS, 40 * MS]], ["a/t1", "a/t2"], 15.0),
    # only the names asked for
    ([["a/x", 40 * MS, 20 * MS], ["a/y", 70 * MS, 30 * MS]], ["a/y"], 30.0),
    # a span wholly over busy time
    ([["a/busy", 15 * MS, 10 * MS]], ["a/busy"], 0.0),
    # a span that runs past the window's end is cut there
    ([["a/late", 90 * MS, 50 * MS]], ["a/late"], 10.0),
])
def test_idle_seconds_under_spans(spans, names, want_ms):
    got = program_spans.idle_seconds(_loaded(OPS, spans), names)
    assert got == pytest.approx(want_ms / 1e3)


@pytest.mark.parametrize("case", ["clocks_disagree", "no_such_span",
                                  "no_window", "not_traced"])
def test_idle_readers_return_none_never_a_number(case):
    spans = [["a/x", 40 * MS, 20 * MS]]
    if case == "clocks_disagree":
        # the device's events lie wholly outside the host's annotation
        loaded = _loaded(OPS, spans, window=(5000 * MS, 5100 * MS))
        assert program_spans.window_and_idle(loaded["raw"]) is None
        assert program_spans.idle_seconds(loaded, ["a/x"]) is None
    elif case == "no_such_span":    # a program from before the spans
        assert program_spans.idle_seconds(_loaded(OPS, []), ["a/x"]) is None
    elif case == "no_window":
        loaded = _loaded(OPS, spans)
        loaded["raw"]["host_spans"] = []
        assert program_spans.idle_seconds(loaded, ["a/x"]) is None
    else:
        bench = types.SimpleNamespace(trace=None, trace_dir="/nonexistent")
        assert program_spans.idle_under(bench, ["a/x"]) is None
        assert program_spans.idle_share_under(bench, ["a/x"]) is None


def test_window_spans_reads_the_ring_by_monotonic_start():
    import time

    from areal_tpu.base import tracing

    with tracing.span("unit/ps", i=0):
        pass
    t_open = time.perf_counter()
    with tracing.span("unit/ps", i=1, waits=[1.0, 2.0]):
        pass
    t_trace = time.perf_counter()
    with tracing.span("unit/ps", i=2, waits=[3.0]):
        pass
    with tracing.span("unit/other"):
        pass
    bench = types.SimpleNamespace(
        t_open=t_open, t_trace=t_trace, t_close=time.perf_counter())
    with tracing.span("unit/ps", i=3):      # after the window closed
        pass
    got = program_spans.window_spans(bench, "unit/ps")
    assert [s["attrs"]["i"] for s in got] == [1, 2]
    got = program_spans.window_spans(bench, "unit/ps", traced_only=True)
    assert [s["attrs"]["i"] for s in got] == [2]
    assert program_spans.window_attr_values(
        bench, "unit/ps", "waits") == [1.0, 2.0, 3.0]
    bench.t_close = None                    # a window that never closed
    assert program_spans.window_spans(bench, "unit/ps") == []
