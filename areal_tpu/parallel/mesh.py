"""Mesh construction and logical-axis sharding rules.

The reference assigns each model a 3D ``ProcessTopology`` (dp, pp, tp —
``realhf/base/topology.py:86,369``) and hand-builds NCCL groups per axis. The
TPU equivalent is declarative: one ``jax.sharding.Mesh`` with named axes

- ``data``: pure data parallelism (params replicated),
- ``fsdp``: data parallelism with params sharded along their "embed" logical
  axis (ZeRO-3 / FSDP — XLA inserts the gathers),
- ``model``: tensor parallelism (heads/mlp/vocab logical axes; XLA inserts
  the psums exactly where Megatron's Column/RowParallelLinear pairs do),

- ``ctx``: context/sequence parallelism — the packed token axis shards over
  it and attention runs as a ring over ICI (``ops/ring_attention.py``),

plus logical→mesh rules mapping each parameter's logical axes (declared in
``areal_tpu.models.transformer.param_logical_axes``) to mesh axes. Pipeline
parallelism is deliberately absent: stages-as-shardings via GSPMD replace the
reference's instruction-based PP engine (SURVEY.md §2.2 row "PP"); expert
parallelism maps the "expert" logical axis onto ``model``.
"""

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """≈ the reference's ``ParallelismConfig`` (``realhf/api/cli_args.py:127``)
    re-expressed as mesh axis sizes.

    ``ctx`` is context/sequence parallelism: the packed TOKEN axis shards
    over it and attention runs as a ring (``ops/ring_attention.py``) — the
    long-context axis the reference reaches through Megatron sequence
    parallelism + varlen flash (SURVEY §2.2 "SP")."""

    data: int = 1
    fsdp: int = 1
    model: int = 1
    ctx: int = 1

    @property
    def world_size(self) -> int:
        return self.data * self.fsdp * self.ctx * self.model

    @classmethod
    def from_str(cls, s: str) -> "ParallelConfig":
        """Parse ``"d2f2c2m2"``-style strings (≈ the reference's ``d4m1p1``
        allocation-mode tokens, with fsdp/ctx replacing pp)."""
        import re

        m = re.fullmatch(r"d(\d+)(?:f(\d+))?(?:c(\d+))?m(\d+)", s)
        if not m:
            raise ValueError(f"Bad parallelism spec: {s!r}")
        return cls(
            data=int(m.group(1)),
            fsdp=int(m.group(2) or 1),
            ctx=int(m.group(3) or 1),
            model=int(m.group(4)),
        )


# logical axis -> mesh axis (None = replicated)
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "layer": None,
    "vocab": "model",
    "heads": "model",
    "mlp": "model",
    "expert": "model",
    "embed": "fsdp",
}


def make_mesh(
    cfg: ParallelConfig, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """Build the global 3D mesh.

    Multi-process runs (after ``jax.distributed.initialize``; see
    ``parallel/multihost.py``) order devices by (process_index, id) so that

    - every ``model`` (TP) group lives inside one process — its psums ride
      ICI, never DCN (the reference pins TP within a node the same way,
      ``realhf/base/topology.py:369``), and
    - each process owns a *contiguous* block of batch rows, which is the
      layout contract of per-host batch feeding
      (``multihost.global_from_local`` / ``fetch_local_rows``).
    """
    if devices is None:
        devices = jax.devices()
    nproc = jax.process_count()
    if nproc > 1:
        devices = sorted(devices, key=lambda d: (d.process_index, d.id))
        if cfg.world_size != len(devices):
            raise ValueError(
                f"multi-host mesh must use all {len(devices)} devices, "
                f"parallel config gives {cfg.world_size}"
            )
        per_proc = len(devices) // nproc
        if per_proc % (cfg.ctx * cfg.model) != 0:
            raise ValueError(
                f"ctx*model={cfg.ctx * cfg.model} groups straddle process "
                f"boundaries ({per_proc} devices/process); keep TP and the "
                "attention ring within a host so they ride ICI"
            )
    if cfg.world_size > len(devices):
        raise ValueError(
            f"Parallel config needs {cfg.world_size} devices, have {len(devices)}"
        )
    devs = np.asarray(devices[: cfg.world_size]).reshape(
        cfg.data, cfg.fsdp, cfg.ctx, cfg.model
    )
    return Mesh(devs, ("data", "fsdp", "ctx", "model"))


def check_tp_divisibility(cfg, tp: int, role: str = "model"):
    """Validate that a ``ModelConfig``'s TP-sharded dims divide by the
    model-axis size — raised at construction, not deep inside a trace."""
    for dim, name in (
        (cfg.n_kv_heads, "n_kv_heads"),
        (cfg.n_q_heads, "n_q_heads"),
        (cfg.vocab_size, "vocab_size"),
    ):
        if dim % tp != 0:
            raise ValueError(
                f"tensor-parallel {role} needs {name} ({dim}) divisible "
                f"by the model-axis size {tp}"
            )


def logical_to_pspec(
    axes: Optional[Tuple[Optional[str], ...]],
    rules: Optional[Dict[str, Optional[str]]] = None,
) -> P:
    """PartitionSpec for one parameter's logical-axis tuple.

    Unknown logical names raise: ``rules.get`` would silently map a typo
    ("vocag") to None — fully replicating a tensor the config meant to
    shard, with no error and an HBM/step-time regression as the only
    symptom. The runtime twin of arealint's ``unknown-mesh-axis`` rule.
    """
    if axes is None:
        return P()
    rules = rules or DEFAULT_RULES
    unknown = [a for a in axes if a is not None and a not in rules]
    if unknown:
        raise ValueError(
            f"unknown logical axis name(s) {unknown} in {axes!r}; the "
            f"sharding rules know {sorted(rules)} — a typo here would "
            "silently replicate the parameter instead of sharding it"
        )
    return P(*(rules.get(a) if a is not None else None for a in axes))


def param_shardings(mesh: Mesh, logical_tree, rules=None):
    """Map a tree of logical-axis tuples to NamedShardings (same
    structure). Validates every logical name via ``logical_to_pspec`` —
    a typo'd axis raises instead of silently replicating the leaf."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_pspec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: x is None or isinstance(x, tuple),
    )


def shard_params(mesh: Mesh, params, logical_tree, rules=None):
    shardings = param_shardings(mesh, logical_tree, rules)
    return jax.device_put(params, shardings)


def batch_pspec() -> P:
    """Packed data buffers are [D, T]: rows spread over both data-parallel
    mesh axes; the token axis shards over ``ctx`` (size 1 = unsharded, the
    per-DP-rank packed batches of the reference; >1 = ring-attention
    context parallelism for long sequences)."""
    return P(("data", "fsdp"), "ctx")
