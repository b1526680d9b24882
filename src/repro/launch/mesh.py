"""Production meshes + logical-axis rules.

``make_production_mesh`` is a *function* (importing this module never
touches jax device state): 16×16 = 256 chips per pod, and 2×16×16 = 512
for the multi-pod dry-run, axes ('pod', 'data', 'model').

Rule sets map the logical axis names used by the model code to mesh axes.
They differ by workload kind:

* train  — batch over (pod, data); FSDP (weight input dims) over data;
  TP dims (heads/mlp/experts/vocab) over model; residual-stream sequence
  sharding over model (sequence parallelism).
* serve  — no FSDP (weights replicated over data, sharded over model so
  per-layer all-gathers never sit on the decode latency path); KV cache
  sequence-sharded over model (split-KV decode).
* gnn    — nodes/edges sharded over every axis (flat 256/512-way).
* recsys — batch over (pod, data); embedding rows over model; candidate
  lists over (pod, data).
"""
from __future__ import annotations

from typing import Dict

import jax

from ..shardlib import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1×1 mesh over the single CPU device: same code path, world size 1."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def _dp(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def rules_train_lm(mesh, batch: int = 0) -> Dict:
    dp = _dp(mesh)
    return {
        "batch": dp, "fsdp": "data", "heads": "model", "kv_heads": "model",
        "mlp": "model", "expert": "model", "vocab": "model", "seq": "model",
        "kv_seq": "model", "model_dim": "model", "layer_stack": None,
        "expert_mlp": None, "embed": None,
    }


def rules_serve_lm(mesh, batch: int) -> Dict:
    dp = _dp(mesh)
    import math
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    batch_ax = dp if batch % max(dp_size, 1) == 0 and batch >= dp_size else None
    return {
        "batch": batch_ax, "fsdp": None, "heads": "model",
        "kv_heads": "model", "mlp": "model", "expert": "model",
        "vocab": "model", "seq": "model", "kv_seq": "model",
        "model_dim": "model", "layer_stack": None, "expert_mlp": None,
        "embed": None,
    }


def rules_gnn(mesh, batch: int = 0) -> Dict:
    dp = _dp(mesh)
    flat = dp + ("model",)
    return {
        "nodes": flat, "edges": flat, "batch": dp, "model_dim": "model",
        "layer_stack": None,
    }


def rules_recsys(mesh, batch: int) -> Dict:
    dp = _dp(mesh)
    import math
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    batch_ax = dp if batch % max(dp_size, 1) == 0 and batch >= dp_size else None
    return {
        "batch": batch_ax, "rows": "model", "model_dim": "model",
        "cand": dp, "layer_stack": None,
    }
