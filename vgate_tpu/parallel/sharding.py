"""Parameter/KV sharding rules over the serving mesh.

Megatron-style tensor parallelism expressed declaratively (SURVEY.md
section 2.2): attention heads and MLP hidden dim shard over ``tp``; MoE
experts shard over ``ep``; XLA inserts the psum/all-gather/all-to-all
collectives over ICI when the jitted programs consume these shardings —
there is no hand-written NCCL-equivalent anywhere.

Rules degrade gracefully: any tensor whose dimension does not divide the
axis size is replicated (e.g. Qwen2.5's 2 KV heads on an 8-way tp mesh),
keeping one code path for 1-chip and N-chip meshes.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vgate_tpu.models.specs import ModelSpec
from vgate_tpu.parallel.mesh import AXIS_EP, AXIS_PP, AXIS_SP, AXIS_TP


def _spec(mesh: Mesh, dims, *axes) -> P:
    """PartitionSpec placing each axis only when the dim divides it."""
    entries = []
    for dim, axis in zip(dims, axes):
        if axis is not None and dim % mesh.shape[axis] == 0 and mesh.shape[axis] > 1:
            entries.append(axis)
        else:
            entries.append(None)
    return P(*entries)


def param_pspecs(spec: ModelSpec, mesh: Mesh) -> Dict[str, Any]:
    """PartitionSpec pytree matching models/decoder.py's param structure."""
    D, L = spec.hidden_size, spec.num_layers
    Q, KVD = spec.q_dim, spec.kv_dim
    F, V, E = spec.intermediate_size, spec.vocab_size, spec.num_experts
    if spec.is_hybrid:
        # plain meshes only (the engine refuses tp/pp/sp/ep for a spec
        # with recurrent layers): every tensor whole on every chip
        from vgate_tpu.models.decoder import init_params

        shapes = jax.eval_shape(
            lambda: init_params(spec, jax.random.PRNGKey(0))
        )
        return jax.tree.map(lambda _: P(), shapes)
    if spec.is_moe:
        F = spec.expert_width

    # the stacked layer axis L shards over pp: each pipeline stage holds
    # only its own layers' weights (and KV pages, kv_pspec below)
    layers: Dict[str, Any] = {
        "input_norm": _spec(mesh, (L, D), AXIS_PP, None),
        "post_norm": _spec(mesh, (L, D), AXIS_PP, None),
        "q": {"w": _spec(mesh, (L, D, Q), AXIS_PP, None, AXIS_TP)},
        "k": {"w": _spec(mesh, (L, D, KVD), AXIS_PP, None, AXIS_TP)},
        "v": {"w": _spec(mesh, (L, D, KVD), AXIS_PP, None, AXIS_TP)},
        "o": {"w": _spec(mesh, (L, Q, D), AXIS_PP, AXIS_TP, None)},
    }
    if spec.qkv_bias:
        layers["q"]["b"] = _spec(mesh, (L, Q), AXIS_PP, AXIS_TP)
        layers["k"]["b"] = _spec(mesh, (L, KVD), AXIS_PP, AXIS_TP)
        layers["v"]["b"] = _spec(mesh, (L, KVD), AXIS_PP, AXIS_TP)
    if spec.ffn_sandwich:
        layers["pre_ffn_norm"] = _spec(mesh, (L, D), AXIS_PP, None)
        layers["post_ffn_norm"] = _spec(mesh, (L, D), AXIS_PP, None)
    if spec.is_moe:
        layers["router"] = _spec(mesh, (L, D, E), AXIS_PP, None, None)
        layers["gate"] = {
            "w": _spec(mesh, (L, E, D, F), AXIS_PP, AXIS_EP, None, AXIS_TP)
        }
        layers["up"] = {
            "w": _spec(mesh, (L, E, D, F), AXIS_PP, AXIS_EP, None, AXIS_TP)
        }
        layers["down"] = {
            "w": _spec(mesh, (L, E, F, D), AXIS_PP, AXIS_EP, AXIS_TP, None)
        }
    else:
        layers["gate"] = {"w": _spec(mesh, (L, D, F), AXIS_PP, None, AXIS_TP)}
        layers["up"] = {"w": _spec(mesh, (L, D, F), AXIS_PP, None, AXIS_TP)}
        layers["down"] = {"w": _spec(mesh, (L, F, D), AXIS_PP, AXIS_TP, None)}

    pspecs: Dict[str, Any] = {
        # vocab-sharded embedding/head: logits all-gather is tiny vs weights
        "embed": _spec(mesh, (V, D), AXIS_TP, None),
        "layers": layers,
        "final_norm": P(),
    }
    if not spec.tie_embeddings:
        pspecs["lm_head"] = _spec(mesh, (D, V), None, AXIS_TP)
    return pspecs


def kv_pspec(
    spec: ModelSpec, mesh: Mesh, num_pages: int = 0
) -> P:
    """KV pages [L, KV, P, page, hd]: layers shard over pp (each stage
    holds its own layers' pages), KV heads over tp when divisible, and —
    when the caller passes a pool size divisible by sp — the page POOL
    over sp (parallel/sp_decode.py: per-chip KV capacity scales with sp,
    the long-context decode path)."""
    return _spec(
        mesh,
        (
            spec.num_layers,
            spec.num_kv_heads,
            # pool shards over sp only for an explicitly divisible size
            # (callers that don't size for sp pass 0 -> replicated)
            num_pages if num_pages else 1,
            1 << 30,
            spec.head_dim,
        ),
        AXIS_PP,
        AXIS_TP,
        AXIS_SP,
        None,
        None,
    )


def named(mesh: Mesh, pspec_tree):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(params, spec: ModelSpec, mesh: Mesh):
    """Place a (host or single-device) param pytree onto the mesh."""
    shardings = named(mesh, param_pspecs(spec, mesh))
    return jax.tree.map(jax.device_put, params, shardings)
