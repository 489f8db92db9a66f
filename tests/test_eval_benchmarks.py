"""Bundled benchmark registry + process-pool grading + aggregation schema.

Covers VERDICT r4 next-round item #6: the five headline benchmarks
(aime24/25, amc23, gpqa_diamond, math_500) ship with the package, render
the reference's prompt templates, and grade through a killable worker
pool with per-item deadlines (``/root/reference/evaluation/
eval_and_aggregate.py``, ``evaluate.py:44-60``)."""

import json
import logging
import os
import time

import pytest

from areal_tpu.evaluation import benchmarks as bm
from areal_tpu.evaluation import grading
from areal_tpu.evaluation.grading import PoolGrader
from areal_tpu.evaluation.mcq import extract_choice, grade_choice


EXPECTED_COUNTS = {
    "aime24": 30, "aime25": 30, "amc23": 40,
    "gpqa_diamond": 198, "math_500": 500,
}


def test_all_benchmarks_load_with_expected_counts():
    assert sorted(bm.benchmark_names()) == sorted(EXPECTED_COUNTS)
    for name, n in EXPECTED_COUNTS.items():
        recs = bm.load_benchmark(name)
        assert len(recs) == n, name
        for r in recs[:5]:
            assert r["prompt"].strip()
            assert r["solutions"][0] != ""
            assert r["task"] in ("math", "gpqa")
            assert r["query_id"].startswith(name)


def test_math_template_rendering():
    recs = bm.load_benchmark("aime24", max_items=1)
    p = recs[0]["prompt"]
    assert p.startswith("<｜User｜>")
    assert "\\boxed{}" in p
    assert p.endswith("<｜Assistant｜><think>\n")
    assert "{input}" not in p


def test_gpqa_template_and_gold_letters():
    recs = bm.load_benchmark("gpqa_diamond")
    assert all(r["solutions"][0] in "ABCD" for r in recs)
    assert "choice letter" in recs[0]["prompt"]
    # options are embedded in the question text
    assert "A." in recs[0]["prompt"]


def test_template_override():
    recs = bm.load_benchmark(
        "math_500", template="qwen25-math-cot", max_items=1
    )
    assert recs[0]["prompt"].startswith("<|im_start|>system")


def test_write_benchmark_jsonl_roundtrip(tmp_path):
    path = bm.write_benchmark_jsonl(
        "amc23", str(tmp_path / "amc23.jsonl"), max_items=3
    )
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert len(lines) == 3
    assert lines[0]["task"] == "math"


def test_mcq_extraction_variants():
    assert extract_choice("blah \\boxed{D}") == "D"
    assert extract_choice("\\boxed{(B)}") == "B"
    assert extract_choice("\\boxed{C. 10^-8 ev}") == "C"
    assert extract_choice("the answer is A") == "A"
    assert extract_choice("no letter here") == ""
    assert grade_choice("thus \\boxed{D}", "D") == 1.0
    assert grade_choice("thus \\boxed{A}", "D") == 0.0


def test_pool_grader_math_and_gpqa():
    with PoolGrader(n_workers=2, timeout_s=10.0) as pool:
        scores = pool.grade([
            ("math", "the answer is \\boxed{7}", ["7"]),
            ("math", "\\boxed{8}", ["7"]),
            ("gpqa", "\\boxed{D}", "D"),
            ("gpqa", "\\boxed{A}", "D"),
        ])
    assert scores[0] > 0 and scores[2] > 0
    assert scores[1] <= 0 and scores[3] == 0.0


def _hang_grader(task, answer, gold):
    if answer.startswith("hang"):
        time.sleep(60)
        open(answer.split(":", 1)[1], "w").close()    # the wedge let go
    return 1.0


def test_pool_grader_kills_wedged_worker(tmp_path, caplog):
    pool = PoolGrader(n_workers=2, timeout_s=1.0, grade_one=_hang_grader)
    let_go = tmp_path / "let_go"
    try:
        with caplog.at_level(logging.WARNING, logger=grading.logger.name):
            scores = pool.grade([
                ("math", "ok", ["1"]),
                ("math", f"hang:{let_go}", ["1"]),
                ("math", "ok", ["1"]),
            ])
        # the scores came back BEFORE the wedged grader let go, by the
        # item's deadline and not by the worker's end: an order of events
        # (no bound on this test's own seconds, which a loaded machine
        # stretches), and the worker was killed, so it never does
        assert not let_go.exists()
        assert [r.getMessage() for r in caplog.records] == [
            "grading item 1 timed out after 1.0s"]
        # timeout scores as a WRONG math answer (-1.0), matching the
        # in-process convention so reward_mean stays comparable
        assert scores == [1.0, -1.0, 1.0]
        assert pool.timeout_cnt == 1
        # pool still serves after the kill/respawn
        assert pool.grade([("math", "ok", ["1"])]) == [1.0]
    finally:
        pool.close()


def test_grade_answers_dispatch_gpqa():
    from areal_tpu.apps.eval_offline import grade_answers

    meta = {"task": "gpqa", "solutions": ["B"]}
    assert grade_answers("q", ["\\boxed{B}", "\\boxed{C}"], meta) == [1.0, 0.0]


def test_aggregate_schema_matches_reference():
    from areal_tpu.apps.eval_offline import aggregate_from_records

    per_prompt = [
        {"rewards": [1.0, -1.0, 1.0, -1.0], "gen_lens": [10, 12, 9, 11],
         "answers": ["\\boxed{1}", "\\boxed{2}", "\\boxed{1}", "\\boxed{3}"],
         "greedy_reward": 1.0, "greedy_len": 10},
        {"rewards": [-1.0, -1.0, -1.0, -1.0], "gen_lens": [8, 8, 8, 8],
         "answers": ["\\boxed{4}"] * 4,
         "greedy_reward": -1.0, "greedy_len": 8},
    ]
    agg = aggregate_from_records(per_prompt, n_sampling=4, path="x.jsonl")
    # the reference's metric-table keys (eval_and_aggregate.py:163-189)
    for key in ("num_questions", "sample_length", "greedy_acc",
                "greedy_length", "sample_pass@1", "pass@1", "pass@2",
                "pass@4"):
        assert key in agg, key
    assert agg["num_questions"] == 2
    assert agg["greedy_acc"] == 0.5
    assert 0.0 < agg["pass@1"] < 1.0
    assert agg["pass@4"] == 0.5


def test_gpqa_metadata_via_prompt_dataset(tmp_path):
    """gpqa records flow through MathCodePromptDataset with task intact."""
    from areal_tpu.api.dataset import DatasetUtility, dataset_metadata, \
        make_dataset

    path = bm.write_benchmark_jsonl(
        "gpqa_diamond", str(tmp_path / "g.jsonl"), max_items=2
    )
    # prompt text needs a tokenizer; reuse prompt_ids to stay hermetic
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    for r in recs:
        r["prompt_ids"] = [1, 2, 3]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    util = DatasetUtility(seed=0, dp_rank=0, world_size=1, tokenizer=None)
    ds = make_dataset("math_code_prompt", util, path=path)
    meta = dataset_metadata(ds)
    assert all(m["task"] == "gpqa" for m in meta.values())
    assert all(m["solutions"][0] in "ABCD" for m in meta.values())


def test_eval_offline_bundled_benchmarks_e2e(tmp_path):
    """VERDICT r4 #6 'Done' criterion: ``eval_offline --benchmark`` over all
    five bundled benchmarks on a tiny random model reproduces the
    reference's metric-table schema (scores ~0 — the model is noise)."""
    import jax
    import numpy as np
    from tokenizers import Tokenizer, models as tok_models, pre_tokenizers
    import transformers

    from areal_tpu.apps import eval_offline
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.models import hf as hf_conv, transformer as tfm

    cfg = ModelConfig(
        n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
        intermediate_dim=64, vocab_size=128, use_attention_bias=True,
        dtype="float32",
    )
    ckpt = str(tmp_path / "ckpt")
    hf_conv.save_hf_checkpoint(
        jax.tree.map(
            lambda x: np.asarray(x), tfm.init_params(cfg, jax.random.key(0))
        ),
        cfg, "qwen2", ckpt,
    )
    # offline word-level tokenizer over the model's 128-token vocab
    vocab = {f"t{i}": i for i in range(126)}
    vocab["[UNK]"], vocab["</s>"] = 126, 127
    tok = Tokenizer(tok_models.WordLevel(vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    transformers.PreTrainedTokenizerFast(
        tokenizer_object=tok, unk_token="[UNK]", eos_token="</s>"
    ).save_pretrained(ckpt)

    out = str(tmp_path / "eval")
    rc = eval_offline.main([
        "--model-path", ckpt, "--output-dir", out,
        "--benchmark", "all", "--max-prompts", "2",
        "--n-sampling", "2", "--max-gen-tokens", "8", "--with-greedy",
        "--batch-prompts", "2", "--grade-workers", "2",
    ])
    assert rc == 0
    agg = json.load(open(os.path.join(out, "aggregate.json")))
    assert set(agg["benchmarks"]) == set(EXPECTED_COUNTS)
    for name, b in agg["benchmarks"].items():
        for key in ("num_questions", "sample_length", "greedy_acc",
                    "greedy_length", "sample_pass@1", "pass@1", "pass@2",
                    "timeout_samples"):
            assert key in b, (name, key)
        assert b["num_questions"] == 2
        samples = os.path.join(out, name, "samples.jsonl")
        lines = [json.loads(line) for line in open(samples)]
        assert len(lines) == 2 and all(len(r["answers"]) == 2 for r in lines)

    # --from-generated re-aggregates without touching the model
    os.remove(os.path.join(out, "aggregate.json"))
    rc = eval_offline.main([
        "--model-path", ckpt, "--output-dir", out,
        "--benchmark", "all", "--max-prompts", "2", "--from-generated",
    ])
    assert rc == 0
    agg2 = json.load(open(os.path.join(out, "aggregate.json")))
    for name in EXPECTED_COUNTS:
        assert agg2["benchmarks"][name]["pass@1"] == \
            agg["benchmarks"][name]["pass@1"]


def test_from_generated_regrades_with_current_verifier(tmp_path):
    """--from-generated re-runs answers through the CURRENT graders (the
    review finding: stale stored rewards must not survive a verifier fix)
    and bypasses the aggregate-exists idempotence guard."""
    from areal_tpu.apps import eval_offline

    out = tmp_path / "eval" / "bench"
    out.mkdir(parents=True)
    data = tmp_path / "bench.jsonl"
    with open(data, "w") as f:
        f.write(json.dumps({
            "query_id": "q0", "prompt_ids": [1, 2], "task": "math",
            "solutions": ["2"],
        }) + "\n")
    # stored sweep: rewards recorded WRONG (pre-fix verifier), answers right
    with open(out / "samples.jsonl", "w") as f:
        f.write(json.dumps({
            "qid": "q0", "answers": ["\\boxed{128 \\mod 3}", "\\boxed{5}"],
            "rewards": [-1.0, -1.0], "gen_lens": [4, 1],
            "no_eos": [False, False],
        }) + "\n")
    # pre-existing aggregate must NOT short-circuit --from-generated
    with open(tmp_path / "eval" / "aggregate.json", "w") as f:
        f.write("{}")
    rc = eval_offline.main([
        "--model-path", "unused", "--output-dir", str(tmp_path / "eval"),
        "--dataset", f"bench={data}", "--from-generated",
        "--grade-workers", "0",
    ])
    assert rc == 0
    agg = json.load(open(tmp_path / "eval" / "aggregate.json"))
    b = agg["benchmarks"]["bench"]
    assert b["pass@1"] == 0.5  # 128 mod 3 == 2 now grades correct
    assert b["pass@2"] == 1.0


def _crash_grader(task, answer, gold):
    if answer == "die":
        os._exit(17)  # simulate a segfault/OOM kill
    return 1.0


def test_pool_grader_detects_dead_worker_fast(caplog):
    """Review finding r5: a CRASHED worker (not a wedge) must be detected
    by liveness, not by waiting out the deadline + spawn allowance."""
    pool = PoolGrader(n_workers=1, timeout_s=30.0, grade_one=_crash_grader)
    try:
        with caplog.at_level(logging.WARNING, logger=grading.logger.name):
            scores = pool.grade([
                ("math", "ok", ["1"]),
                ("math", "die", ["1"]),
                ("math", "ok", ["1"]),
            ])
        # the branch that scored the item is the liveness one, which does
        # not wait for timeout_s (30) + SPAWN_ALLOWANCE (120): an event,
        # not a bound on this test's own seconds
        assert [r.getMessage() for r in caplog.records] == [
            "grading item 1 worker died"]
        assert scores == [1.0, -1.0, 1.0]
        assert pool.timeout_cnt == 1
    finally:
        pool.close()
