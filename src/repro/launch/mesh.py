"""Production mesh + per-(arch, cell) logical-axis rule construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state). Single pod: (data=16, model=16) = 256 chips; multi-pod adds a
leading pod axis: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

from ..configs.base import ModelConfig, ShapeCell


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"production mesh needs {n} devices, have {len(devs)} — the "
            "dry-run entry point sets XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def _div(n: int, by: int) -> bool:
    return n > 0 and n % by == 0


def build_rules(cfg: ModelConfig, cell: Optional[ShapeCell] = None,
                *, multi_pod: bool = False,
                model_size: int = 16, data_size: int = 16,
                overrides: Optional[dict] = None) -> dict:
    """Megatron-style logical->mesh rules, specialized per arch and cell.

    Activation axes ("*_act") only map to a mesh axis when the runtime dim
    divides it; parameter axes are flattened head*dim products which always
    divide for the assigned archs. batch=1 cells idle the data axis and
    (where possible) shard the KV-cache sequence dim over it instead.
    """
    dp = ("pod", "data") if multi_pod else ("data",)
    total_dp = data_size * (2 if multi_pod else 1)

    batch = cell.global_batch if cell else None
    rules: dict = {
        # params
        "layers": None,
        "embed": None,
        "heads": "model",        # flattened n_heads*head_dim param dim
        "kv_heads": "model",     # flattened kv*head_dim param dim
        "mlp": "model",
        "vocab": "model",
        "experts": "model",      # EP
        "expert_mlp": None,
        "ssm_inner": "model",
        "ssm_heads": "model",
        # activations
        "batch": dp,
        "seq": None,
        "cache_seq": None,
        "heads_act": "model" if _div(cfg.n_heads, model_size) else None,
        "kv_heads_act": "model" if _div(cfg.n_kv_heads, model_size) else None,
    }

    if batch is not None and not _div(batch, total_dp):
        # batch unshardable (e.g. long_500k batch=1): idle the data axis for
        # activations; shard the cache sequence dim over it instead (the
        # flash-decoding layout) when the cell is a decode cell.
        rules["batch"] = None
        if cell and cell.kind == "decode":
            rules["cache_seq"] = dp
    import os
    naive = os.environ.get("REPRO_NAIVE", "0") == "1"
    if (cell and cell.kind == "decode" and rules["kv_heads_act"] is None
            and not naive):
        # opt H2 (flash-decoding layout): when kv heads cannot shard over
        # "model" (MQA / non-divisible head counts), shard the cache SEQ dim
        # there instead — otherwise the cache is replicated 16x and decode
        # reads are 16x the roofline minimum.
        cs = rules.get("cache_seq")
        existing = () if cs is None else ((cs,) if isinstance(cs, str) else
                                          tuple(cs))
        flat = []
        for a in existing:
            flat.extend(a if isinstance(a, tuple) else (a,))
        rules["cache_seq"] = tuple(flat) + ("model",)
    if overrides:
        rules.update(overrides)
    return rules


def param_shardings(mesh, specs_tree):
    """Logical-spec pytree -> NamedSharding pytree (under active rules)."""
    from jax.sharding import NamedSharding

    from ..distributed.sharding import logical_to_spec

    def to_sharding(spec):
        return NamedSharding(mesh, logical_to_spec(spec))

    return jax.tree.map(to_sharding, specs_tree,
                        is_leaf=lambda s: isinstance(s, tuple))
