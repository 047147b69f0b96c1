"""Logical-axis sharding rules per (config × shape-kind × mesh).

The parallelism recipe:

  * ``train`` / ``prefill``: DP over (pod, data); Megatron-style TP over
    ``model`` (attention head dims, MLP hidden, vocab/embedding); EP for MoE
    experts over ``model`` (shard_map path with a psum combine); sequence
    stays unsharded (the chunked-attention scan bounds activation memory).
  * ``decode``: batch over (pod, data); the KV cache is sequence-sharded
    over ``model`` — attention contracts head_dim locally and reduces the
    tiny softmax statistics across ``model`` (flash-decode in SPMD form).
  * ``long`` (batch=1 decode): no batch to shard — recurrent/conv states and
    window caches are sharded over every axis (data and model).

Activation head-count constraints are applied only when the head count
divides the axis (otherwise left to propagation); flattened weight dims
(H*hd etc.) always divide the 16-way model axis for the assigned archs.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.transformer import kv_major
from .mesh import data_axes


def logical_rules(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    dp = data_axes(mesh)
    dp_entry = dp if len(dp) > 1 else dp[0]
    n_model = mesh.shape["model"]
    long_ctx = shape.kind == "decode" and shape.global_batch < mesh.shape[dp[0]]

    if cfg.parallelism == "fsdp" and shape.kind in ("train", "prefill"):
        return _fsdp_rules(cfg, shape, mesh, dp)
    if cfg.parallelism == "fsdp_ep" and shape.kind in ("train", "prefill"):
        # MoE hybrid: experts stay expert-parallel over `model` (tokens move,
        # not banks); dense weights are fully sharded over (data, model) and
        # gathered per layer; batch shards over data only so the EP psum
        # combine applies.
        rules = _fsdp_rules(cfg, shape, mesh, dp)
        rules["batch"] = dp if len(dp) > 1 else dp[0]
        rules["experts"] = "model"
        return rules
    if cfg.parallelism == "ep_a2a" and shape.kind in ("train", "prefill"):
        # full EP: tokens sharded over every axis, local scatter dispatch,
        # all-to-all token exchange with the expert shards over `model`.
        rules = _fsdp_rules(cfg, shape, mesh, dp)
        rules["experts"] = "model"
        return rules

    seq_rule = None
    if (
        cfg.sequence_parallel
        and shape.kind in ("train", "prefill")
        and shape.seq_len % n_model == 0
    ):
        seq_rule = "model"  # sequence parallelism (Megatron SP)
    rules = {
        "batch": dp_entry,
        "seq": seq_rule,
        "embed": None,
        "layers": None,
        # weight dims (flattened head dims — always divisible)
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        # activation dims (only when they divide the axis)
        "act_heads": "model" if cfg.n_heads % n_model == 0 else None,
        "act_kv": "model" if cfg.n_kv_heads % n_model == 0 else None,
        "act_ff": "model",
        "act_vocab": "model",
        "inner_seq": None,
        # decode cache axes
        "cache_seq": "model" if shape.kind == "decode" else None,
        "state": None,
    }
    if long_ctx:
        # batch=1: spread states/caches over everything available
        rules["batch"] = None
        rules["ff"] = dp + ("model",)
        rules["cache_seq"] = dp_entry
        rules["act_heads"] = None
        rules["act_kv"] = None
    return rules


def _fsdp_rules(cfg: ModelConfig, shape: ShapeConfig, mesh, dp: tuple) -> dict:
    """Fully-sharded data parallelism: the batch spreads over every mesh
    axis; weight matrices shard over (data..., model) on their wide dims and
    GSPMD gathers them per layer (collective volume ~ weights, independent
    of the batch).  Falls back to model-only sharding on dims that the full
    axis product does not divide."""
    all_axes = dp + ("model",)
    n_all = mesh.size

    def wide(dim_size: int):
        if dim_size % n_all == 0:
            return all_axes
        return "model" if dim_size % mesh.shape["model"] == 0 else None

    from repro.models.model import padded_vocab

    batch_ok = shape.global_batch % n_all == 0
    return {
        "batch": all_axes if batch_ok else (dp if len(dp) > 1 else dp[0]),
        "seq": None,
        "embed": None,
        "layers": None,
        "heads": wide(cfg.q_dim),
        "kv_heads": wide(cfg.kv_dim),
        "ff": wide(max(cfg.d_ff, cfg.d_inner if cfg.family == "ssm" else 0,
                       cfg.lru_width if cfg.family == "hybrid" else 0) or 1),
        "vocab": wide(padded_vocab(cfg)),
        "experts": "model",
        "act_heads": None,
        "act_kv": None,
        "act_ff": None,
        "act_vocab": None,
        "inner_seq": None,
        "cache_seq": None,
        "state": None,
    }


def batch_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """PartitionSpecs for the input batch pytree (follows the 'batch' rule,
    so TP/FSDP/long-context layouts stay consistent)."""
    dp_entry = logical_rules(cfg, shape, mesh)["batch"]
    specs = {"inputs": P(dp_entry, None)}
    if shape.kind == "train":
        specs["targets"] = P(dp_entry, None)
    if cfg.embeds_input:
        specs["embeds"] = P(dp_entry, None, None)
        if cfg.rope == "mrope":
            specs["positions"] = P(None, dp_entry, None)
    if cfg.family == "encdec":
        specs["frames"] = P(dp_entry, None, None)
    return specs


def cache_pspecs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """PartitionSpecs for the decode cache pytree (mirrors
    Model.abstract_cache structure)."""
    rules = logical_rules(cfg, shape, mesh)
    b = rules["batch"]
    cseq = rules["cache_seq"]
    kvh = rules["act_kv"]
    ff = rules["ff"]
    kv = P(None, b, kvh, cseq, None) if kv_major(cfg) else P(None, b, cseq, kvh, None)
    if cfg.family in ("dense", "moe"):
        return {"k": kv, "v": kv}
    if cfg.family == "ssm":
        return {
            "conv": P(None, b, None, ff),
            "ssm": P(None, b, ff, None),
        }
    if cfg.family == "hybrid":
        return {
            "conv": P(None, b, None, ff),
            "rec": P(None, b, ff),
            "k": kv,
            "v": kv,
        }
    if cfg.family == "encdec":
        ckv = P(None, b, None, kvh, None)
        return {"k": kv, "v": kv, "cross_k": ckv, "cross_v": ckv}
    raise ValueError(cfg.family)


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )
