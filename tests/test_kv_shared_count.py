"""The program's count of shared K/V pages against the benchmark's.

``benchmark/resident.py:ChunkResident`` counts from the TRAFFIC what a
decode chunk holds once a slot and once a distinct page (the numerator of
the decode-attention rooflines and ``gen.kv_shared_share``); the engine
counts from its page TABLE what the kernel's programs are given and read
(``kv_pages_named``, ``kv_pages_read`` on every ``gen_engine/chunk``).
Tier 1 collects ``tests/`` only, so this is where the two are held
together: a closed loop of GRPO groups through an engine that runs the
paged kernel (interpret mode), the driver's count beside every chunk.
"""

import time

import jax
import numpy as np
import pytest

from areal_tpu.base import tracing
from areal_tpu.gen.engine import GenerationEngine, GenRequest
from areal_tpu.models import transformer as tfm
from areal_tpu.models.config import ModelConfig
from benchmark import traffic_gen
from benchmark.resident import ChunkResident

CFG = ModelConfig(
    n_layers=1, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=128, dtype="float32",
)
PAGE, STEPS = 8, 4
# the rollout cells' traffic at a size the interpreter gets through: 16
# clients, groups of 8 on one prompt, prompts of 2-5 whole pages (a group
# block for every four rows, as in the cells: ``prefix_plan``)
MIX = {
    "clients": 16, "group_size": 8, "n_groups": 4, "shape_seed": 20260927,
    "prompt_len": {"dist": "uniform", "lo": 20, "hi": 44},
    "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.8,
                   "lo": 3, "hi": 40},
}


@pytest.fixture(scope="module")
def loop():
    """``(chunk attributes, (per_slot, distinct))`` of every chunk of the
    driver's closed loop (``benchmark/drivers/rollout_inproc.py``)."""
    params = tfm.init_params(CFG, jax.random.key(0))
    eng = GenerationEngine(
        CFG, params, max_slots=MIX["clients"], max_seqlen=96,
        max_new_tokens_cap=40, page_size=PAGE, seed=0)
    eng._decode_use_pallas = True
    stream = traffic_gen.RequestStream(MIX, 2**31 + 7, CFG.vocab_size)
    count = ChunkResident(PAGE, STEPS)
    live, chunks = {}, []

    def submit(req):
        eng.submit(GenRequest(
            rid=req.rid, input_ids=req.prompt,
            max_new_tokens=req.max_new_tokens, temperature=1.0))
        live[req.rid] = {"req": req, "chunks": 0}

    for req in stream.initial():
        submit(req)
    for _ in range(22):
        mark = time.perf_counter()
        outs = eng.step(STEPS)
        attrs = [r["attrs"] for r in tracing.spans_since(mark)
                 if r["name"] == "gen_engine/chunk"
                 and r["attrs"].get("slots")]
        counted = count.count(
            list(live.values())[: len(live) - eng.n_pending()])
        assert len(attrs) == 1
        chunks.append((attrs[0], counted))
        for o in outs:
            live.pop(o.rid)
            submit(next(stream))
    return chunks


def test_groups_share_in_the_loop(loop):
    """The loop exercises what it is there for: past the opening
    population most chunks seat rows of two or three groups."""
    sharing = [a for a, _ in loop if a["kv_shared_rows"]]
    assert len(sharing) >= len(loop) // 2
    assert max(a["kv_shared_groups"] for a, _ in loop) >= 2


def test_program_and_traffic_count_the_same_pages(loop):
    """Pages the kernel is spared, ``kv_pages_named - kv_pages_read``,
    against the traffic's ``(per_slot - distinct) / page``, chunk by
    chunk: within a page a shared row (the traffic's count is taken midway
    through the chunk and keys rows by the prompt they submitted, the
    program's at the chunk's first step from the table: a member admitted
    in the wave that computes its group's prompt names no filed page
    yet)."""
    for attrs, (per_slot, distinct) in loop:
        program = attrs["kv_pages_named"] - attrs["kv_pages_read"]
        traffic = (per_slot - distinct) // PAGE
        assert abs(program - traffic) <= max(attrs["kv_shared_rows"], 1), (
            attrs, per_slot, distinct)
    named = sum(a["kv_pages_named"] for a, _ in loop)
    read = sum(a["kv_pages_read"] for a, _ in loop)
    per_slot = sum(c[0] for _, c in loop)
    distinct = sum(c[1] for _, c in loop)
    # ... and over the loop the two shares are one number to within the
    # two points the benchmark's cells are held to
    assert 0.05 < 1 - distinct / per_slot < 0.6
    assert abs((1 - read / named) - (1 - distinct / per_slot)) < 0.05
