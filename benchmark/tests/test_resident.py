"""``python -m pytest benchmark/tests`` (CPU, no engine): the resident
count of ``benchmark/resident.py`` and the readers that divide by it.

The cases of the count are ONE parametrised test; the readers are driven
with a hand-made trace and hand-made spans, so every expected number is
worked out here and not by the code under test."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import program_spans, traffic_gen
from benchmark.resident import ChunkResident, traced_ratio
from benchmark.run import HERE, REHEARSAL_EXIT, ROOT, load_json, load_reader

PAGE, STEPS = 128, 16


def _rec(prompt, max_new=4096, chunks=0):
    return {"req": traffic_gen.Request("r", list(prompt), max_new),
            "chunks": chunks}


def _group(n_rows, prompt_len, first_token=1, **kw):
    return [_rec([first_token] * prompt_len, **kw) for _ in range(n_rows)]


def _old_loop(records, decode_steps):
    """The loop the seven drivers copied until PR 49, as it stood."""
    res = 0
    for rec in records:
        r = rec["req"]
        res += len(r.prompt) - 1 + min(
            r.max_new_tokens,
            rec["chunks"] * decode_steps + decode_steps // 2)
    return res


def _recorded_population():
    """Cell 1's opening population and the head of its stream, part-way."""
    mix = load_json(HERE, "traffic", "grpo16_closed128.json")
    stream = traffic_gen.RequestStream(mix, 2**31 + 5, 1000)
    reqs = stream.initial()[:40] + [next(stream) for _ in range(40)]
    return [{"req": r, "chunks": (7 * i) % 23} for i, r in enumerate(reqs)]


CASES = {
    # name: (records, shared whole pages: per_slot - distinct, in pages)
    "group_size_1": ([_rec([i] * 640) for i in range(1, 17)], 0),
    "16_rows_of_one_640_token_prompt": (_group(16, 640), 15 * 4),
    "641_tokens_fill_five_pages": (_group(16, 641), 15 * 5),
    "100_token_prompt_has_no_whole_page": (_group(16, 100), 0),
    "one_member_left": (_group(1, 640) + [_rec([2] * 700)], 0),
    "two_groups_do_not_share_with_each_other": (
        _group(3, 640, first_token=1) + _group(2, 300, first_token=2),
        2 * 4 + 1 * 2),
    "progress_is_not_shared": (_group(4, 640, chunks=50), 3 * 4),
    "a_short_output_caps_the_progress": (_group(2, 257, max_new=5), 1 * 2),
    "recorded_population": (_recorded_population(), None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_chunk_resident(name):
    records, shared_pages = CASES[name]
    want_per_slot = _old_loop(records, STEPS)
    before = [r["chunks"] for r in records]
    per_slot, distinct = ChunkResident(PAGE, STEPS).count(records)
    assert per_slot == want_per_slot        # the old loop's value, to the digit
    assert [r["chunks"] for r in records] == [c + 1 for c in before]
    if shared_pages is None:    # the recorded one: by a second, slower count
        groups = {}
        for r in records:
            groups.setdefault(tuple(r["req"].prompt), []).append(r)
        shared_pages = sum(
            (len(g) - 1) * ((len(p) - 1) // PAGE) for p, g in groups.items())
        assert shared_pages > 0     # the stream's groups do share
    assert per_slot - distinct == shared_pages * PAGE
    assert 0 < distinct <= per_slot


def test_pending_rows_are_not_counted():
    """The driver hands over the RUNNING rows only: the newest submissions
    still pending hold no slot, are not counted and do not advance."""
    live = {f"r{i}": rec for i, rec in enumerate(_group(16, 640))}
    n_pending = 6
    running = list(live.values())[: len(live) - n_pending]
    per_slot, distinct = ChunkResident(PAGE, STEPS).count(running)
    assert per_slot == 10 * (639 + 8)
    assert distinct == per_slot - 9 * 4 * PAGE
    assert [r["chunks"] for r in live.values()] == [1] * 10 + [0] * 6


def _bench(per_slot, distinct, op_total_s, k=2, **facts):
    return types.SimpleNamespace(
        trace={"op_total_s": op_total_s},
        peaks={"hbm_bytes_per_s": 800e9},
        facts=dict(chunk_resident_tokens=per_slot,
                   chunk_distinct_tokens=distinct, **facts),
        span_records=lambda name, traced_only=False: [(0.0, 0.1)] * k,
        arch=load_json(ROOT, "benchmark", "configs", "r1d-qwen-1p5b.json"),
        t_open=0.0, t_trace=1.0, t_close=2.0,
    )


OPS = {     # label -> (seconds, events)
    "jit_chunk/%paged_decode.9 bf16[128,12,128]": (0.5, 56),
    "jit_chunk/%fused_sample.7 tpu_custom_call": (0.2, 2),
    "jit_chunk/%kv_page_write.7 tpu_custom_call": (0.1, 2),
    "jit_extend/%paged_decode.3": (9.0, 1),     # another program's
}


@pytest.mark.parametrize("distinct, want_tokens", [
    ([900, 1000, 3000], 4000),      # equal lists: the per-slot reading
    ([900, 600, 1800], 2400),       # 40 % shared over the traced chunks
])
def test_paged_reader_counts_distinct_bytes_over_the_named_kernel(
        distinct, want_tokens):
    bench = _bench([900, 1000, 3000], distinct, OPS,
                   decode_steps=16, kv_bytes_per_token=28_672)
    got = load_reader("kernel.paged_decode_roofline").read(bench)
    # the last k = 2 chunks, 16 steps each, over %paged_decode's 0.5 s alone
    assert got == pytest.approx(
        100.0 * want_tokens * 16 * 28_672 / 800e9 / 0.5)
    bench.facts.pop("chunk_distinct_tokens")
    assert load_reader("kernel.paged_decode_roofline").read(bench) is None


def test_traced_ratio_and_shared_share():
    bench = _bench([900, 1000, 3000], [900, 600, 1800], OPS)
    assert traced_ratio(bench) == pytest.approx(2400 / 4000)
    assert load_reader("gen.kv_shared_share").read(bench) == pytest.approx(
        100.0 * (1 - 3300 / 4900))
    bench.facts["chunk_distinct_tokens"] = []
    assert traced_ratio(bench) is None
    assert load_reader("gen.kv_shared_share").read(bench) is None


@pytest.mark.parametrize("reader, config, attrs, per_slot_bytes, full_bytes", [
    # bytes of ONE step at ratio 1, and the part of them the ratio scales
    ("kernel.looped_decode_roofline", "ouro-2p6b-l8",
     {"cache_layers": 32, "resident_tokens": 1000}, 1000 * 262_144, None),
    ("kernel.mla_decode_roofline", "joyai-flash-l5",
     {"resident_tokens": 1000}, 1000 * 1_152 * 5, None),
    ("kernel.cca_decode_roofline", "zaya1-8b-l16",
     {"resident_tokens": 1000}, 1000 * 16_384, None),
    ("kernel.hybrid_decode_roofline", "smallthinker-21b-l8",
     {"resident_tokens": 1000, "window_resident_tokens": 700},
     1000 * 4_096 + 700 * 12_288, 1000 * 4_096),
    ("kernel.yoco_decode_roofline", "phi4-mini-flash",
     {"resident_tokens": 1000, "window_resident_tokens": 300},
     5_120 * (8 * 1000 + 8 * 300), 5_120 * 8 * 1000),
])
@pytest.mark.parametrize("ratio", [1.0, 0.6])
def test_span_readers_scale_the_full_attention_bytes(
        monkeypatch, reader, config, attrs, per_slot_bytes, full_bytes, ratio):
    """The per-slot count stays the program's span's; the driver's ratio
    scales the full-attention bytes and leaves a window kind's alone. With
    ``chunk_distinct_tokens == chunk_resident_tokens`` the reading is the
    per-slot one."""
    ops = {
        "jit_chunk/%paged_decode.5 bf16[...]": (0.25, 10),
        "jit_chunk/%paged_decode_window.45 bf16[...]": (0.25, 10),
        "jit_chunk/%mla_decode.18 bf16[...]": (0.5, 10),
        "jit_chunk/%fused_sample.7": (0.3, 2),
    }
    per_slot = [500, 1000, 1000]
    bench = _bench(per_slot, [500] + [int(1000 * ratio)] * 2, ops)
    bench.arch = load_json(ROOT, "benchmark", "configs", config + ".json")
    spans = [{"name": "gen_engine/chunk", "attrs": dict(attrs, steps=16)}] * 2
    monkeypatch.setattr(
        program_spans, "window_spans", lambda b, name, traced_only=False: spans)
    scaled = per_slot_bytes if full_bytes is None else full_bytes
    want_bytes = 2 * 16 * (per_slot_bytes - scaled + scaled * ratio)
    got = load_reader(reader).read(bench)
    assert got == pytest.approx(100.0 * want_bytes / 800e9 / 0.5)


def test_rehearsal_reports_the_shared_share():
    """A cell's rehearsal (the traffic file's ``rehearse`` block, as
    ``benchmark.selfcheck`` runs it): groups of four over prompts of at
    least one whole page, so the share is a number above 0."""
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "r1d-1p5b.rollout", "--seed", str(2**31 + 11), "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == REHEARSAL_EXIT, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] and "gen.kv_shared_share" in last["counts_only"]
