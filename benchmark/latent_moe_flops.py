"""Bytes of a LatentMoE expert layer of which this chip holds an
expert-parallel rank's SHARE (family ``nemotron_h``), from shapes alone,
and which ops of a device trace are its routed experts and its latent
projections. For the ``moe.local_*`` readers under ``layer_metrics/``.

``arch`` is a configuration file's dict with the keys of the family's
public ``config.json``: ``n_routed_experts`` counts the experts HELD here
(the router scores ``n_routed_experts x expert_parallel_size``), each two
matrices of ``moe_latent_size x moe_intermediate_size`` (no gate matrix);
``hybrid_override_pattern`` names the blocks, ``E`` the expert ones.

What the ALGORITHM needs of a decode step: the two matrices of every held
expert that a row chose, read once (the engine's ``moe_held_experts_hit``
census on its ``gen_engine/chunk`` spans: held experts with a row, summed
over the expert blocks and the steps). An expert held here that no row
chose is not read, and an expert of another rank is not here to read.

Finding the ops, as ``benchmark/moe_flops.py`` finds its own: by the
largest operand, which for every op that multiplies by them is the expert
blocks' stack of routed matrices ``[blocks, held, latent, F]`` (up) or
``[blocks, held, F, latent]`` (down) in the stored dtype: the
``moe_grouped`` kernel is handed the whole stacks, and XLA's einsums fuse
their slice of them. The latent projections are ``[blocks, hidden,
latent]`` and ``[blocks, latent, hidden]``. Loop and call ops carry the
same arrays in their tuples and cover their bodies, so they are left out.
A program without such stacks (another family, or the parent of the PR
that added this one) has no such op, and the functions return ``None``.
"""

import re
from typing import Optional

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
_COVERING = ("while", "call", "conditional", "async-start", "async-done")


def _sizes(arch: dict):
    """``(expert blocks, experts held, hidden, latent, expert width)``, or
    None for a configuration without a latent expert layer."""
    if "moe_latent_size" not in arch or "hybrid_override_pattern" not in arch:
        return None
    pattern = arch["hybrid_override_pattern"][: arch["num_hidden_layers"]]
    return (pattern.count("E"), arch["n_routed_experts"], arch["hidden_size"],
            arch["moe_latent_size"], arch["moe_intermediate_size"])


def held_expert_bytes(arch: dict, itemsize: int = 2) -> int:
    """One held expert's up and down matrices at the stored width."""
    return 2 * arch["moe_latent_size"] * arch["moe_intermediate_size"] * itemsize


def held_expert_flops(arch: dict) -> int:
    """Multiply-adds x 2 of one row through one expert (two matmuls)."""
    return 4 * arch["moe_latent_size"] * arch["moe_intermediate_size"]


def _pattern(arch: dict, shapes, program: Optional[str]) -> "re.Pattern":
    dt = _SHORT[arch["serving_dtype"]]
    prog = re.escape(program) if program else r"[^/]+"
    dims = "|".join(",".join(str(d) for d in s) for s in shapes)
    return re.compile(
        rf"^{prog}/\S+ (?!(?:{'|'.join(_COVERING)})\b)\S+ .*"
        rf"<- {dt}\[(?:{dims})\]$")


def _seconds(bench, shapes, program: Optional[str]) -> Optional[float]:
    rx = _pattern(bench.arch, shapes, program)
    hits = [v[0] for k, v in bench.trace["op_total_s"].items() if rx.search(k)]
    return sum(hits) if hits else None


def routed_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """Summed device seconds, inside the traced window, of the ops that
    stream the held routed experts' stacks; only ``program``'s if given."""
    sizes = None if bench.trace is None else _sizes(bench.arch)
    if sizes is None:
        return None
    n, held, _, lat, width = sizes
    return _seconds(
        bench, [(n, held, lat, width), (n, held, width, lat)], program)


def latent_op_seconds(bench, program: Optional[str] = None) -> Optional[float]:
    """The same of the latent down- and up-projection."""
    sizes = None if bench.trace is None else _sizes(bench.arch)
    if sizes is None:
        return None
    n, _, hidden, lat, _ = sizes
    return _seconds(bench, [(n, hidden, lat), (n, lat, hidden)], program)
