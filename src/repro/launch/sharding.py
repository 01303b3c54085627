"""Sharding rules: map every param/activation leaf to a PartitionSpec.

Federated training layout (fed mesh, axes ("fed","dp","tp") [+ "pod"]):
  * every param leaf carries a leading node dim F  -> fed axes
  * last weight dim                                 -> 'tp'   (tensor par.)
  * largest remaining divisible dim                 -> 'dp'   (FSDP/ZeRO-3)
  * batch (F, B, ...)                               -> (fed axes, 'dp')

Serving layout (production mesh, axes ("data","model") [+ "pod"]):
  * last weight dim -> 'model'; largest remaining -> 'data' (+'pod') FSDP
  * batch dim -> ('pod','data') when divisible, else replicated
  * KV caches: kv-head dim over 'model' when divisible, else seq dim.

Rules are structural (shape-based), so they cover every architecture's
pytree without per-arch tables; GSPMD inserts the collectives implied by
the specs.
"""
from __future__ import annotations

import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _assign(shape, axes_sizes, skip_dims=()):
    """Greedy: assign ('tp', size) to the last divisible dim, then 'dp' to
    the largest remaining divisible dim. Returns list of axis-or-None."""
    spec = [None] * len(shape)
    used = set(skip_dims)
    for name, size in axes_sizes:
        if size <= 1:
            continue
        cands = [i for i in range(len(shape)) if i not in used]
        # largest divisible dim first (vocab > d_ff > d_model); tie -> later
        order = sorted(cands, key=lambda i: (-shape[i], -i))
        for i in order:
            if shape[i] % size == 0 and shape[i] >= size:
                spec[i] = name
                used.add(i)
                break
    return spec


# leaves smaller than this are replicated: sharding a (d,) norm scale or
# bias drags the activations it multiplies into d-sharding, and every
# following matmul all-gathers the residual (dry-run: 182GB/step).
SMALL_PARAM = 1 << 16

# Megatron-style tensor parallelism by param name:
#   column-parallel (tp on d_out, the default): wq/wk/wv, w_gate/w_up, ...
#   row-parallel    (tp on d_in = dim -2):      wo, w_down, w_out
# Row-parallel consumes the head-/ffn-sharded activation LOCALLY and
# all-reduces the (b,s,d_model) output; without it XLA all-gathers the
# f32 activation per matmul (dry-run: 75GB/step on qwen3 train_4k).
# Embedding tables (V, d) are vocab-parallel (also dim -2).
# KV projections are row-parallel too: with kv_heads < tp a column-parallel
# wk/wv splits single heads across devices and every use reshards; row-
# parallel replicates the (small) KV heads on all tp devices — the standard
# GQA tensor-parallel layout.
ROW_PARALLEL = {"wo", "w_down", "w_out", "table", "wk", "wv"}


def _inner_spec(shape, name, tp_name, tp, fsdp_name, fsdp_size):
    """Sharding for the weight dims (no leading fed/F dim here)."""
    spec = [None] * len(shape)
    tp_dim = None
    if name in ROW_PARALLEL and len(shape) >= 2 \
            and shape[-2] % tp == 0 and shape[-2] >= tp:
        tp_dim = len(shape) - 2
    elif shape[-1] % tp == 0 and shape[-1] >= tp:
        tp_dim = len(shape) - 1
    else:
        # fallback: largest divisible dim
        for i in sorted(range(len(shape)), key=lambda i: (-shape[i], -i)):
            if shape[i] % tp == 0 and shape[i] >= tp:
                tp_dim = i
                break
    if tp_dim is not None and tp > 1:
        spec[tp_dim] = tp_name
    if fsdp_size and fsdp_size > 1:
        for i in sorted(range(len(shape)), key=lambda i: (-shape[i], -i)):
            if i != tp_dim and shape[i] % fsdp_size == 0 \
                    and shape[i] >= fsdp_size:
                spec[i] = fsdp_name
                break
    return spec


def fed_param_spec(shape, mesh: Mesh, fsdp: bool = True,
                   name: str | None = None) -> P:
    """Param leaf with leading F node dim on a fed mesh.

    fsdp=False: params replicated over dp within a node (small models —
    avoids per-matmul weight all-gathers when the replica easily fits)."""
    fed = ("pod", "fed") if "pod" in mesh.axis_names else "fed"
    if int(np.prod(shape[1:], initial=1)) < SMALL_PARAM:
        return P(fed, *([None] * (len(shape) - 1)))
    inner = _inner_spec(shape[1:], name, "tp", mesh.shape["tp"],
                        "dp", mesh.shape["dp"] if fsdp else 0)
    return P(fed, *inner)


def serve_param_spec(shape, mesh: Mesh, fsdp: bool = True,
                     name: str | None = None) -> P:
    """Param leaf (no F dim) on the production mesh."""
    if int(np.prod(shape, initial=1)) < SMALL_PARAM:
        return P(*([None] * len(shape)))
    inner = _inner_spec(shape, name, "model", mesh.shape["model"],
                        "data", mesh.shape["data"] if fsdp else 0)
    return P(*inner)


def fed_batch_spec(shape, mesh: Mesh) -> P:
    """Batch leaf (F, B, ...) on a fed mesh."""
    fed = ("pod", "fed") if "pod" in mesh.axis_names else "fed"
    spec = [fed] + [None] * (len(shape) - 1)
    if len(shape) > 1 and shape[1] % mesh.shape["dp"] == 0 \
            and shape[1] >= mesh.shape["dp"]:
        spec[1] = "dp"
    return P(*spec)


def serve_batch_spec(shape, mesh: Mesh) -> P:
    """Batch leaf (B, ...) on the production mesh."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    total = int(np.prod([mesh.shape[a] for a in axes]))
    if shape and shape[0] % total == 0 and shape[0] >= total:
        return P(tuple(axes), *([None] * (len(shape) - 1)))
    # try data axis only
    if shape and "data" in mesh.axis_names \
            and shape[0] % mesh.shape["data"] == 0 \
            and shape[0] >= mesh.shape["data"]:
        return P("data", *([None] * (len(shape) - 1)))
    return P(*([None] * len(shape)))


def cache_spec(shape, mesh: Mesh) -> P:
    """KV cache leaf (L, B, S, KV, D) or SSM state (L, B, H, D, N)."""
    model = mesh.shape["model"]
    spec = [None] * len(shape)
    # batch dim (index 1) over data when divisible
    if len(shape) > 1 and shape[1] % mesh.shape["data"] == 0 \
            and shape[1] >= mesh.shape["data"]:
        spec[1] = "data"
    # a head-ish dim over model: prefer dim -2 (kv heads / ssm heads)
    for i in (len(shape) - 2, len(shape) - 3, len(shape) - 1):
        if 1 < i < len(shape) and spec[i] is None \
                and shape[i] % model == 0 and shape[i] >= model:
            spec[i] = "model"
            break
    return P(*spec)

