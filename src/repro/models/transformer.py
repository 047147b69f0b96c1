"""Transformer assembly: blocks, scanned layer stacks, and decode paths for
all five families (dense / moe / ssm / hybrid / encdec).

Layer parameters are stacked on a leading ``layers`` dim and consumed with
``lax.scan`` (keeps the HLO small at 60+ layers); the hybrid family's
interleaved (rec, rec, attn) pattern uses a python loop over per-layer
slices instead.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs import scopes

from .common import constrain
from .layers import (
    apply_norm,
    apply_rope,
    attn_output,
    gqa_attention,
    mlp_apply,
    qkv_project,
)
from .moe import moe_apply
from .rglru import recurrent_block
from .ssm import mamba_block


def _remat(fn, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.remat == "save_collectives":
        # full remat EXCEPT collective outputs (MoE a2a results): recompute
        # compute-cheap work, never re-pay the wire
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names("moe_out")
        )
    return fn


# ---------------------------------------------------------------------------
# Blocks (full-sequence)
# ---------------------------------------------------------------------------


def attn_block(x, lp, cfg, dt, positions, *, causal=True, local_window=0,
               cross_kv=None, collect_cache=False):
    """Pre-norm attention sub-block. Returns (x, (k, v) or None)."""
    with scopes.scope("qkv"):
        h = apply_norm(cfg.norm, x, lp["ln1"], lp.get("ln1_b"))
        # pin the (bf16) norm output to the residual layout so under SP the
        # all-gather crosses in bf16, not the norm's f32 internals (§Perf It3)
        h = constrain(h, "batch", "seq", "embed")
        q, k, v = qkv_project(h, lp["attn"], cfg, dt)
        if cross_kv is None:
            q = apply_rope(cfg.rope, q, positions, cfg.rope_theta)
            k = apply_rope(cfg.rope, k, positions, cfg.rope_theta)
        else:
            k, v = cross_kv
            causal = False
        q = constrain(q, "batch", "inner_seq", "act_heads", None)
        k = constrain(k, "batch", "inner_seq", "act_kv", None)
    with scopes.scope("attn_core"):
        o = gqa_attention(
            q, k, v, causal=causal, impl=cfg.attn_impl, chunk=cfg.attn_chunk,
            local_window=local_window,
        )
    # the residual add inside the scope: a fusion takes its root's metadata
    with scopes.scope("attn_out"):
        x = x + attn_output(o, lp["attn"], cfg, dt)
        x = constrain(x, "batch", "seq", "embed")
    return x, ((k, v) if collect_cache else None)


def ffn_block(x, lp, cfg, dt, mesh_info):
    with scopes.scope("ffn"):
        h = apply_norm(cfg.norm, x, lp["ln2"], lp.get("ln2_b"))
        h = constrain(h, "batch", "seq", "embed")
        if cfg.family == "moe":
            y = moe_apply(h, lp["mlp"], cfg, dt, mesh_info)
        else:
            y = mlp_apply(cfg.mlp, h, lp["mlp"], dt)
        x = x + y
        return constrain(x, "batch", "seq", "embed")


def dense_layer(x, lp, cfg, dt, positions, mesh_info, *, causal=True,
                local_window=0, collect_cache=False):
    x, kv = attn_block(
        x, lp, cfg, dt, positions, causal=causal, local_window=local_window,
        collect_cache=collect_cache,
    )
    x = ffn_block(x, lp, cfg, dt, mesh_info)
    return x, kv


def mamba_layer(x, lp, cfg, dt, collect_cache=False):
    h = apply_norm(cfg.norm, x, lp["ln1"], lp.get("ln1_b"))
    y, conv_state, ssm_state = mamba_block(h, lp["mamba"], cfg, dt)
    x = constrain(x + y, "batch", "seq", "embed")
    return x, ((conv_state, ssm_state) if collect_cache else None)


def rec_layer(x, lp, cfg, dt, collect_cache=False):
    h = apply_norm(cfg.norm, x, lp["ln1"], lp.get("ln1_b"))
    y, conv_state, rec_state = recurrent_block(h, lp["rec"], cfg, dt)
    x = constrain(x + y, "batch", "seq", "embed")
    x = ffn_block(x, lp, cfg, dt, None)
    return x, ((conv_state, rec_state) if collect_cache else None)


# ---------------------------------------------------------------------------
# Stacks (full-sequence forward; optionally collect decode caches)
# ---------------------------------------------------------------------------


def scan_stack(x, layers, body):
    """lax.scan over stacked layer params; body(x, lp) -> (x, extras)."""
    def f(carry, lp):
        return body(carry, lp)

    return jax.lax.scan(f, x, layers)


def forward_stack(params, cfg, x, positions, mesh_info, *, causal=True,
                  collect_cache=False):
    """Homogeneous stacks (dense / moe / ssm)."""
    if cfg.family == "ssm":
        body = _remat(lambda h, lp: mamba_layer(h, lp, cfg, cfg_dtype(cfg), collect_cache), cfg)
    else:
        body = _remat(
            lambda h, lp: dense_layer(
                h, lp, cfg, cfg_dtype(cfg), positions, mesh_info,
                causal=causal, collect_cache=collect_cache,
            ),
            cfg,
        )
    with scopes.scope("layers"):
        x, extras = scan_stack(x, params["layers"], body)
    return x, extras


def forward_hybrid(params, cfg, x, positions, mesh_info, *, collect_cache=False):
    """recurrentgemma: python loop over the (rec, rec, attn) pattern."""
    dt = cfg_dtype(cfg)
    rec_i = attn_i = 0
    rec_caches, attn_caches = [], []
    for li in range(cfg.n_layers):
        kind = cfg.block_pattern[li % len(cfg.block_pattern)]
        if kind == "rec":
            lp = jax.tree.map(lambda a: a[rec_i], params["rec_layers"])
            fn = _remat(lambda h, lp=lp: rec_layer(h, lp, cfg, dt, collect_cache), cfg)
            x, cache = fn(x)
            rec_caches.append(cache)
            rec_i += 1
        else:
            lp = jax.tree.map(lambda a: a[attn_i], params["attn_layers"])

            def attn_fn(h, lp=lp):
                h, kv = dense_layer(
                    h, lp, cfg, dt, positions, mesh_info,
                    causal=True, local_window=cfg.local_window,
                    collect_cache=collect_cache,
                )
                return h, kv

            x, kv = _remat(attn_fn, cfg)(x)
            attn_caches.append(kv)
            attn_i += 1
    if not collect_cache:
        return x, None
    stack = lambda caches: jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    return x, (stack(rec_caches), stack(attn_caches))


def forward_encoder(params, cfg, frames, mesh_info):
    """whisper encoder over precomputed (stub) frame embeddings."""
    from .layers import sinusoidal_embedding

    dt = cfg_dtype(cfg)
    B, F, d = frames.shape
    pos = jnp.arange(F)[None, :]
    x = frames.astype(dt) + sinusoidal_embedding(pos, d).astype(dt)
    body = _remat(
        lambda h, lp: dense_layer(h, lp, cfg, dt, pos, mesh_info, causal=False),
        cfg,
    )
    x, _ = scan_stack(x, params["enc_layers"], body)
    return apply_norm(cfg.norm, x, params["enc_norm"], params.get("enc_norm_b"))


def forward_decoder(params, cfg, x, positions, enc_out, mesh_info, *,
                    collect_cache=False):
    """whisper decoder: self-attention + cross-attention per layer."""
    dt = cfg_dtype(cfg)

    def body(h, lp):
        h, self_kv = attn_block(
            h, lp, cfg, dt, positions, causal=True, collect_cache=collect_cache
        )
        # cross-attention: kv projected from encoder output
        hc = apply_norm(cfg.norm, h, lp["lnc"], lp.get("lnc_b"))
        cast = lambda w: w.astype(dt)
        cp = lp["cross"]
        kc = enc_out @ cast(cp["wk"])
        vc = enc_out @ cast(cp["wv"])
        Bq = hc.shape[0]
        kc = kc.reshape(Bq, -1, cfg.n_kv_heads, cfg.head_dim)
        vc = vc.reshape(Bq, -1, cfg.n_kv_heads, cfg.head_dim)
        qc = (hc @ cast(cp["wq"])).reshape(Bq, -1, cfg.n_heads, cfg.head_dim)
        oc = gqa_attention(qc, kc, vc, causal=False, impl=cfg.attn_impl, chunk=cfg.attn_chunk)
        h = h + oc.reshape(Bq, -1, cfg.q_dim) @ cast(cp["wo"])
        h = ffn_block(h, lp, cfg, dt, mesh_info)
        extras = (self_kv, (kc, vc)) if collect_cache else None
        return h, extras

    return scan_stack(x, params["dec_layers"], _remat(body, cfg))


# ---------------------------------------------------------------------------
# Decode (single token against a cache)
# ---------------------------------------------------------------------------


def kv_major(cfg) -> bool:
    """Whether the decode cache's k/v stacks are stored [L, B, KV, S, hd],
    the order in which the Pallas decode kernel reads a layer's blocks in
    place; the jnp paths keep the layer scan's [L, B, S, KV, hd]."""
    return cfg.attn_impl == "pallas"


def _decode_attn(x, lp, cfg, dt, k_all, v_all, layer, pos, *, window: int = 0):
    """One-token attention of layer ``layer`` against the whole cache stack
    k_all, v_all (ordered as ``kv_major`` says): writes the new k/v in place
    at ``pos`` (or ``pos % window`` for ring caches) and attends to that
    layer's slots.  Returns (x, k_all, v_all)."""
    B = x.shape[0]
    with scopes.scope("qkv"):
        h = apply_norm(cfg.norm, x, lp["ln1"], lp.get("ln1_b"))
        positions = jnp.full((B, 1), pos, jnp.int32)
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(positions, (3, B, 1))
        q, k, v = qkv_project(h, lp["attn"], cfg, dt)
        q = apply_rope(cfg.rope, q, positions, cfg.rope_theta)
        k = apply_rope(cfg.rope, k, positions, cfg.rope_theta)
    slot = pos % window if window else pos
    with scopes.scope("kv_write"):
        if kv_major(cfg):  # [B, 1, KV, hd] -> [B, KV, 1, hd]
            k, v, at = k.swapaxes(1, 2), v.swapaxes(1, 2), (layer, 0, 0, slot, 0)
        else:
            at = (layer, 0, slot, 0, 0)
        k_all = jax.lax.dynamic_update_slice(k_all, k.astype(k_all.dtype)[None], at)
        v_all = jax.lax.dynamic_update_slice(v_all, v.astype(v_all.dtype)[None], at)
    with scopes.scope("attn_core"):
        S = k_all.shape[3 if kv_major(cfg) else 2]
        if kv_major(cfg) and not window and S % 128 == 0:
            # flash-decode kernel: the layer's KV blocks stream HBM->VMEM
            # once, straight out of the stack (in its stored dtype — fp8
            # caches halve the traffic), scores stay in VMEM
            from repro.kernels.ops import decode_attention_stacked

            o = decode_attention_stacked(q[:, 0], k_all, v_all, layer, pos + 1)
            o = o.astype(dt)[:, None]
        else:
            k_cache, v_cache = k_all[layer], v_all[layer]
            if kv_major(cfg):
                k_cache, v_cache = k_cache.swapaxes(1, 2), v_cache.swapaxes(1, 2)
            if window:
                # ring buffer: mask by stored-position recency
                idx = jnp.arange(S)
                ring_pos = pos - ((slot - idx) % S)  # absolute position stored per slot
                valid = (ring_pos >= 0) & (ring_pos >= pos - window + 1)
                o = _masked_decode_attention(q, k_cache, v_cache, valid, cfg)
            else:
                o = gqa_attention(
                    q, k_cache.astype(dt), v_cache.astype(dt), causal=False,
                    impl="naive", q_offset=pos, kv_len=pos + 1,
                )
    with scopes.scope("attn_out"):
        x = x + attn_output(o, lp["attn"], cfg, dt)
    return x, k_all, v_all


def _masked_decode_attention(q, k_cache, v_cache, valid, cfg):
    B, S, KV, hd = k_cache.shape
    H = cfg.n_heads
    G = H // KV
    q5 = q.reshape(B, 1, KV, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q5, k_cache.astype(q.dtype),
                   preferred_element_type=jnp.float32) / (hd**0.5)
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(q.dtype))
    return o.reshape(B, 1, H, hd)


def decode_stack(params, cfg, x, cache, pos, mesh_info):
    """dense / moe decode: scan over the layer params, carrying the whole
    cache, which each layer writes in place (no per-layer slice of it goes
    in or out of the scan, so the step's output aliases the donated input)."""
    dt = cfg_dtype(cfg)

    def body(carry, inp):
        h, k_all, v_all = carry
        lp, layer = inp
        h, k_all, v_all = _decode_attn(h, lp, cfg, dt, k_all, v_all, layer, pos)
        h = ffn_block(h, lp, cfg, dt, mesh_info)
        return (h, k_all, v_all), None

    layers = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    with scopes.scope("layers"):
        (x, k, v), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]), (params["layers"], layers))
    return x, {"k": k, "v": v}


def decode_ssm(params, cfg, x, cache, mesh_info):
    dt = cfg_dtype(cfg)

    def body(h, inp):
        lp, conv_s, ssm_s = inp
        hn = apply_norm(cfg.norm, h, lp["ln1"], lp.get("ln1_b"))
        y, new_conv, new_ssm = mamba_block(
            hn, lp["mamba"], cfg, dt, conv_state=conv_s, ssm_state=ssm_s
        )
        return h + y, (new_conv, new_ssm)

    x, (conv_new, ssm_new) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["ssm"])
    )
    return x, {"conv": conv_new, "ssm": ssm_new}


def decode_hybrid(params, cfg, x, cache, pos, mesh_info):
    dt = cfg_dtype(cfg)
    rec_i = attn_i = 0
    new_conv, new_rec = [], []
    k_all, v_all = cache["k"], cache["v"]
    for li in range(cfg.n_layers):
        kind = cfg.block_pattern[li % len(cfg.block_pattern)]
        if kind == "rec":
            lp = jax.tree.map(lambda a: a[rec_i], params["rec_layers"])
            hn = apply_norm(cfg.norm, x, lp["ln1"], lp.get("ln1_b"))
            from .rglru import recurrent_block

            y, cs, rs = recurrent_block(
                hn, lp["rec"], cfg, dt,
                conv_state=cache["conv"][rec_i], rec_state=cache["rec"][rec_i],
            )
            x = x + y
            x = ffn_block(x, lp, cfg, dt, None)
            new_conv.append(cs)
            new_rec.append(rs)
            rec_i += 1
        else:
            lp = jax.tree.map(lambda a: a[attn_i], params["attn_layers"])
            x, k_all, v_all = _decode_attn(
                x, lp, cfg, dt, k_all, v_all, attn_i, pos, window=cfg.local_window,
            )
            x = ffn_block(x, lp, cfg, dt, None)
            attn_i += 1
    return x, {
        "conv": jnp.stack(new_conv),
        "rec": jnp.stack(new_rec),
        "k": k_all,
        "v": v_all,
    }


def decode_encdec(params, cfg, x, cache, pos, mesh_info):
    """whisper decode: self-attn against the self cache (carried and written
    in place, as in ``decode_stack``) + cross-attn against the prefilled
    cross kv."""
    dt = cfg_dtype(cfg)

    def body(carry, inp):
        h, k_all, v_all = carry
        lp, layer, ck, cv = inp
        h, k_all, v_all = _decode_attn(h, lp, cfg, dt, k_all, v_all, layer, pos)
        hc = apply_norm(cfg.norm, h, lp["lnc"], lp.get("lnc_b"))
        cast = lambda w: w.astype(dt)
        cp = lp["cross"]
        B = hc.shape[0]
        qc = (hc @ cast(cp["wq"])).reshape(B, 1, cfg.n_heads, cfg.head_dim)
        oc = gqa_attention(qc, ck.astype(dt), cv.astype(dt), causal=False, impl="naive")
        h = h + oc.reshape(B, 1, cfg.q_dim) @ cast(cp["wo"])
        h = ffn_block(h, lp, cfg, dt, mesh_info)
        return (h, k_all, v_all), None

    layers = jnp.arange(cache["k"].shape[0], dtype=jnp.int32)
    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["dec_layers"], layers, cache["cross_k"], cache["cross_v"]),
    )
    return x, {"k": k, "v": v, "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}


def cfg_dtype(cfg):
    return jnp.dtype(cfg.compute_dtype)
