"""The one general traffic generator: reads a mix file, makes the work.

A mix (``benchmark/traffic/<name>.json``) is data: length distributions,
sharing, client count, set sizes. Two kinds of work are made here, chosen
by which parameters the mix has, never by its name:

* a request stream (``clients`` present): GRPO-style groups that share a
  prompt, each request with its own output length, for a closed loop;
* training batches (``max_tokens_per_batch`` present): packed rollout
  batches with behaviour log-probs and rewards.

Every ``--seed`` gets the SAME sizes: the lengths are drawn once from
``shape_seed`` (a constant of the mix), and the run's seed draws the token
ids and values and permutes the sizes only where that cannot change the
work a window holds: the output lengths within one group of the stream,
the sequences within one training batch. The order of the groups and of
the batches is the same for every seed, because a window consumes only the
head of the stream: with the whole order left to the seed, six seeds gave
six different subsets of lengths and 1.6 % of spread in tokens/s where two
runs of one seed differed by 0.1 % (chip runs, PR 23). So the spread
between seeds is the system's, not the dice's.
"""

import dataclasses
from typing import Dict, Iterator, List, Tuple

import numpy as np


def draw_lengths(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``spec``: {"dist": "uniform"|"lognormal"|"fixed", ...} -> n ints."""
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, spec["value"], np.float64)
    elif dist == "uniform":
        x = rng.integers(spec["lo"], spec["hi"] + 1, n).astype(np.float64)
    elif dist == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("lo", 1), spec.get("hi", 1 << 30)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, int(n)).tolist()


@dataclasses.dataclass
class Request:
    rid: str
    prompt: List[int]       # what is submitted (shared prompt + progress)
    max_new_tokens: int


class RequestStream:
    """Closed-loop rollout work. ``initial()`` is the population the window
    opens on: one request per client, part-way through its output, drawn
    length-biased (a long request holds its slot longer, so a loop in
    steady state holds more of them than the stream does). ``__next__``
    is the stream that replaces finished requests: consecutive groups of
    ``group_size`` requests share one prompt."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        shape = np.random.default_rng(mix["shape_seed"])
        g, k = mix["n_groups"], mix["group_size"]
        prompt_lens = draw_lengths(mix["prompt_len"], shape, g)
        out_lens = draw_lengths(mix["output_len"], shape, g * k)
        n0 = mix["clients"]
        p = out_lens / out_lens.sum()
        pick = shape.choice(g * k, size=n0, replace=False, p=p)
        self._init_sizes = [
            (int(prompt_lens[i // k]), int(out_lens[i]),
             int(shape.integers(0, out_lens[i])))
            for i in pick
        ]
        order = np.random.default_rng([seed, 1])
        self._prompt_lens = prompt_lens
        self._out_lens = np.concatenate([
            order.permutation(out_lens[i * k:(i + 1) * k]) for i in range(g)
        ])
        self._i = 0

    def initial(self) -> List[Request]:
        out = []
        for j, (plen, olen, done) in enumerate(self._init_sizes):
            rng = np.random.default_rng([self.seed, 2, j])
            out.append(Request(
                rid=f"init-{j}", prompt=_tokens(rng, plen + done, self.vocab),
                max_new_tokens=olen - done,
            ))
        return out

    def __iter__(self) -> Iterator[Request]:
        return self

    def __next__(self) -> Request:
        i, k = self._i, self.mix["group_size"]
        self._i += 1
        n = len(self._out_lens)
        lap, j = divmod(i, n)
        grp = j // k
        plen = int(self._prompt_lens[grp])
        rng = np.random.default_rng([self.seed, 3, lap, grp])
        return Request(
            rid=f"r{i}", prompt=_tokens(rng, plen, self.vocab),
            max_new_tokens=int(self._out_lens[j]),
        )


@dataclasses.dataclass
class TrainBatch:
    seqlens: List[int]
    input_ids: np.ndarray       # concatenated
    prompt_mask: np.ndarray
    behav_logprobs: np.ndarray  # 0 on prompt positions
    rewards: np.ndarray         # one per sequence


def train_batches(mix: Dict, seed: int, vocab: int) -> List[TrainBatch]:
    """``n_batches`` packed batches of whole sequences, each at most
    ``max_tokens_per_batch`` tokens: sequences are taken in order, and when
    one does not fit the next ``lookahead`` are tried before the batch is
    closed (what a packer over a larger rollout batch does)."""
    shape = np.random.default_rng(mix["shape_seed"])
    cap, nb = mix["max_tokens_per_batch"], mix["n_batches"]
    n_draw = nb * (cap // mix["prompt_len"].get("lo", 1) + 1)
    plens = draw_lengths(mix["prompt_len"], shape, n_draw)
    rlens = draw_lengths(mix["response_len"], shape, n_draw)
    queue: List[Tuple[int, int]] = list(zip(plens.tolist(), rlens.tolist()))
    sizes: List[List[Tuple[int, int]]] = []
    for _ in range(nb):
        room, batch, skipped = cap, [], []
        while queue and len(skipped) <= mix.get("lookahead", 0):
            p, r = queue.pop(0)
            if p + r <= room:
                batch.append((p, r))
                room -= p + r
            else:
                skipped.append((p, r))
        queue[:0] = skipped
        sizes.append(batch)
    order = np.random.default_rng([seed, 1])
    out = []
    for b in range(nb):
        rng = np.random.default_rng([seed, 2, b])
        ids, pm, lp, lens = [], [], [], []
        for j in order.permutation(len(sizes[b])):
            p, r = sizes[b][j]
            ids.append(rng.integers(1, vocab, p + r))
            pm.append(np.r_[np.ones(p, bool), np.zeros(r, bool)])
            seq_lp = np.zeros(p + r, np.float32)
            # behaviour policy log-probs of the response tokens, stored at
            # the position that predicts each (label-aligned, as the
            # rollout workers store them): a stale policy near the current
            seq_lp[p - 1: p + r - 1] = rng.normal(
                mix["behav_logprob_mean"], mix["behav_logprob_std"], r
            )
            lp.append(seq_lp)
            lens.append(p + r)
        out.append(TrainBatch(
            seqlens=lens,
            input_ids=np.concatenate(ids).astype(np.int64),
            prompt_mask=np.concatenate(pm),
            behav_logprobs=np.concatenate(lp),
            # continuous, so that no batch has all-equal rewards (which
            # would make the advantages, and the gradient, exactly zero)
            rewards=rng.normal(0.0, 1.0, len(lens)).astype(np.float32),
        ))
    return out
