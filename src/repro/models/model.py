"""Model facade: parameter templates, init, loss / prefill / decode entry
points for every architecture family.

The parameter *template* (``build_template``) is the single source of truth
for parameter shapes, initializers and logical sharding axes; it backs
``init_params`` (real arrays), ``abstract_params`` (ShapeDtypeStructs for
the dry-run) and ``param_pspecs`` (PartitionSpecs for pjit) — plus the
CAPre access-plan analysis, which walks the same tree."""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.obs import scopes

from .common import (
    ParamSpec,
    abstract_from_template,
    constrain,
    init_from_template,
    param_count,
    pspecs_from_template,
)
from .layers import sinusoidal_embedding
from .transformer import (
    cfg_dtype,
    decode_encdec,
    decode_hybrid,
    decode_ssm,
    decode_stack,
    forward_decoder,
    forward_encoder,
    forward_hybrid,
    forward_stack,
    kv_major,
)

# ---------------------------------------------------------------------------
# Parameter templates
# ---------------------------------------------------------------------------


def _stack(tmpl: dict, n: int) -> dict:
    """Add a leading stacked-layers dim to every ParamSpec."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        tmpl,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _attn_tmpl(cfg: ModelConfig, cross: bool = False) -> dict:
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    t = {
        "wq": ParamSpec((d, qd), ("embed", "heads")),
        "wk": ParamSpec((d, kvd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, kvd), ("embed", "kv_heads")),
        "wo": ParamSpec((qd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias and not cross:
        t["bq"] = ParamSpec((qd,), ("heads",), init="zeros")
        t["bk"] = ParamSpec((kvd,), ("kv_heads",), init="zeros")
        t["bv"] = ParamSpec((kvd,), ("kv_heads",), init="zeros")
    if cfg.attn_out_bias and not cross:
        t["bo"] = ParamSpec((d,), ("embed",), init="zeros")
    if cfg.qk_norm and not cross:
        t["q_norm"] = ParamSpec((hd,), (None,), init="ones")
        t["k_norm"] = ParamSpec((hd,), (None,), init="ones")
    return t


def _norm_tmpl(cfg: ModelConfig, name: str) -> dict:
    t = {name: ParamSpec((cfg.d_model,), ("embed",), init="ones")}
    if cfg.norm == "layernorm":
        t[f"{name}_b"] = ParamSpec((cfg.d_model,), ("embed",), init="zeros")
    return t


def _mlp_tmpl(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp in ("swiglu", "geglu"):
        t = {
            "wi_gate": ParamSpec((d, f), ("embed", "ff")),
            "wi_up": ParamSpec((d, f), ("embed", "ff")),
            "wo": ParamSpec((f, d), ("ff", "embed")),
        }
    else:  # gelu / relu2
        t = {
            "wi": ParamSpec((d, f), ("embed", "ff")),
            "wo": ParamSpec((f, d), ("ff", "embed")),
        }
        if cfg.mlp_bias:
            t["bi"] = ParamSpec((f,), ("ff",), init="zeros")
    if cfg.mlp_bias:
        t["bo"] = ParamSpec((d,), ("embed",), init="zeros")
    return t


def _moe_tmpl(cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((d, E), ("embed", None)),
        "we_gate": ParamSpec((E, d, f), ("experts", "embed", None)),
        "we_up": ParamSpec((E, d, f), ("experts", "embed", None)),
        "we_down": ParamSpec((E, f, d), ("experts", None, "embed")),
    }


def _mamba_tmpl(cfg: ModelConfig) -> dict:
    d, di, N, R, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    return {
        "in_proj": ParamSpec((d, 2 * di), ("embed", "ff")),
        "conv_w": ParamSpec((di, K), ("ff", None)),
        "conv_b": ParamSpec((di,), ("ff",), init="zeros"),
        "x_proj": ParamSpec((di, R + 2 * N), ("ff", None)),
        "dt_w": ParamSpec((R, di), (None, "ff")),
        "dt_b": ParamSpec((di,), ("ff",), init="zeros"),
        "A_log": ParamSpec((di, N), ("ff", None), init="ones"),
        "D": ParamSpec((di,), ("ff",), init="ones"),
        "out_proj": ParamSpec((di, d), ("ff", "embed")),
    }


def _rec_tmpl(cfg: ModelConfig) -> dict:
    d, w, K = cfg.d_model, cfg.lru_width, cfg.ssm_conv
    return {
        "wy": ParamSpec((d, w), ("embed", "ff")),
        "wx": ParamSpec((d, w), ("embed", "ff")),
        "conv_w": ParamSpec((w, K), ("ff", None)),
        "conv_b": ParamSpec((w,), ("ff",), init="zeros"),
        "w_a": ParamSpec((w, w), ("ff", None)),
        "w_x": ParamSpec((w, w), ("ff", None)),
        "lam": ParamSpec((w,), ("ff",), init="ones"),
        "out_w": ParamSpec((w, d), ("ff", "embed")),
    }


def _layer_tmpl(cfg: ModelConfig) -> dict:
    """One decoder layer for dense/moe families: nested sublayer subtrees."""
    t = {}
    t.update(_norm_tmpl(cfg, "ln1"))
    t.update(_norm_tmpl(cfg, "ln2"))
    t["attn"] = _attn_tmpl(cfg)
    t["mlp"] = _moe_tmpl(cfg) if cfg.family == "moe" else _mlp_tmpl(cfg)
    return t


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab rows padded to a multiple of 256 so the embedding/lm-head shard
    evenly on any model axis up to 256 (Megatron-style vocab padding; the
    padded logits train to -inf and are never valid targets)."""
    return -(-cfg.vocab_size // 256) * 256


def build_template(cfg: ModelConfig) -> dict:
    V, d = padded_vocab(cfg), cfg.d_model
    base = {"embed": ParamSpec((V, d), ("vocab", "embed"), scale=0.01)}
    if not cfg.tie_embeddings:
        base["lm_head"] = ParamSpec((d, V), ("embed", "vocab"), scale=0.01)
    base.update(_norm_tmpl(cfg, "final_norm"))

    if cfg.family in ("dense", "moe"):
        base["layers"] = _stack(_layer_tmpl(cfg), cfg.n_layers)
    elif cfg.family == "ssm":
        lt = {}
        lt.update(_norm_tmpl(cfg, "ln1"))
        lt["mamba"] = _mamba_tmpl(cfg)
        base["layers"] = _stack(lt, cfg.n_layers)
    elif cfg.family == "hybrid":
        pattern = cfg.block_pattern
        kinds = [pattern[i % len(pattern)] for i in range(cfg.n_layers)]
        n_rec, n_attn = kinds.count("rec"), kinds.count("attn")
        rec = {}
        rec.update(_norm_tmpl(cfg, "ln1"))
        rec.update(_norm_tmpl(cfg, "ln2"))
        rec["rec"] = _rec_tmpl(cfg)
        rec["mlp"] = _mlp_tmpl(cfg)
        attn = {}
        attn.update(_norm_tmpl(cfg, "ln1"))
        attn.update(_norm_tmpl(cfg, "ln2"))
        attn["attn"] = _attn_tmpl(cfg)
        attn["mlp"] = _mlp_tmpl(cfg)
        base["rec_layers"] = _stack(rec, n_rec)
        base["attn_layers"] = _stack(attn, n_attn)
    elif cfg.family == "encdec":
        enc = {}
        enc.update(_norm_tmpl(cfg, "ln1"))
        enc.update(_norm_tmpl(cfg, "ln2"))
        enc["attn"] = _attn_tmpl(cfg)
        enc["mlp"] = _mlp_tmpl(cfg)
        dec = dict(enc)
        dec.update(_norm_tmpl(cfg, "lnc"))
        dec["cross"] = _attn_tmpl(cfg, cross=True)
        base["enc_layers"] = _stack(enc, cfg.enc_layers)
        base["dec_layers"] = _stack(dec, cfg.n_layers)
        base.update({f"enc_norm{k[10:]}": v for k, v in _norm_tmpl(cfg, "final_norm").items()})
    else:
        raise ValueError(f"unknown family {cfg.family}")
    return base


def count_params_config(cfg: ModelConfig, active_only: bool = False) -> int:
    tmpl = build_template(cfg)
    total = param_count(tmpl)
    if active_only and cfg.family == "moe":
        expert_total = param_count(
            {k: v for k, v in tmpl["layers"]["mlp"].items() if k.startswith("we_")}
        )
        frac = cfg.experts_per_token / cfg.n_experts
        total -= int(expert_total * (1.0 - frac))
    return total


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.template = build_template(cfg)

    # -- params -------------------------------------------------------------

    def init_params(self, rng) -> dict:
        return _init_params(self.cfg, rng)

    def abstract_params(self) -> dict:
        return abstract_from_template(self.template, jnp.dtype(self.cfg.param_dtype))

    def param_pspecs(self, rules: dict) -> dict:
        return pspecs_from_template(self.template, rules)

    # -- embedding / head ----------------------------------------------------

    def embed(self, params, tokens):
        dt = cfg_dtype(self.cfg)
        with scopes.scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
            return constrain(x, "batch", "seq", "embed")

    def logits(self, params, h):
        cfg = self.cfg
        dt = cfg_dtype(cfg)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        # bf16 operands with f32 accumulation: halves the wire bytes of the
        # all-gather feeding the vocab-sharded head matmul (§Perf It2/It3)
        h = constrain(h.astype(dt), "batch", "seq", "embed")
        out = jnp.matmul(h, w.astype(dt), preferred_element_type=jnp.float32)
        return constrain(out, "batch", "inner_seq", "act_vocab")

    def _final_norm(self, params, h):
        from .layers import apply_norm

        with scopes.scope("logits"):
            return apply_norm(self.cfg.norm, h, params["final_norm"], params.get("final_norm_b"))

    # -- full-sequence forward -------------------------------------------------

    def hidden_states(self, params, batch, mesh_info=None, collect_cache=False):
        cfg = self.cfg
        dt = cfg_dtype(cfg)
        if cfg.family == "encdec":
            enc_out = forward_encoder(params, cfg, batch["frames"], mesh_info)
            B, S = batch["inputs"].shape
            pos = jnp.arange(S)[None, :]
            x = self.embed(params, batch["inputs"])
            x = x + sinusoidal_embedding(pos, cfg.d_model).astype(dt)
            h, extras = forward_decoder(
                params, cfg, x, pos, enc_out, mesh_info, collect_cache=collect_cache
            )
            return self._final_norm(params, h), (extras, enc_out)
        if cfg.embeds_input and "embeds" in batch:
            x = batch["embeds"].astype(dt)
            B, S = x.shape[:2]
        else:
            x = self.embed(params, batch["inputs"])
            B, S = batch["inputs"].shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            if cfg.rope == "mrope":
                positions = jnp.broadcast_to(positions[None], (3, B, S))
        if cfg.family == "hybrid":
            h, extras = forward_hybrid(
                params, cfg, x, positions, mesh_info, collect_cache=collect_cache
            )
        else:
            h, extras = forward_stack(
                params, cfg, x, positions, mesh_info, collect_cache=collect_cache
            )
        return self._final_norm(params, h), extras

    # -- training loss -----------------------------------------------------------

    def loss_fn(self, params, batch, mesh_info=None):
        cfg = self.cfg
        h, _ = self.hidden_states(params, batch, mesh_info)
        targets = batch["targets"]
        if cfg.loss_chunk and cfg.loss_chunk < h.shape[1]:
            return self._chunked_loss(params, h, targets)
        logits = self.logits(params, h)
        return _ce_loss(logits, targets)

    def _chunked_loss(self, params, h, targets):
        cfg = self.cfg
        C = cfg.loss_chunk
        B, S, d = h.shape
        n = S // C
        hc = h[:, : n * C].reshape(B, n, C, d).transpose(1, 0, 2, 3)
        tc = targets[:, : n * C].reshape(B, n, C).transpose(1, 0, 2)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

        def body(acc, inp):
            hb, tb = inp
            logits = hb.astype(jnp.float32) @ w.astype(jnp.float32)
            return acc + _ce_loss(logits, tb) * tb.size, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc))
        return total / (B * n * C)

    # -- serving -------------------------------------------------------------------

    def prefill(self, params, batch, mesh_info=None):
        """Full forward; returns (last-token logits, decode cache)."""
        cfg = self.cfg
        h, extras = self.hidden_states(params, batch, mesh_info, collect_cache=True)
        with scopes.scope("logits"):
            logits = self.logits(params, h[:, -1:, :])[..., : cfg.vocab_size]
        with scopes.scope("kv_write"):
            cache = self._assemble_cache(batch, extras)
        return logits, cache

    def _assemble_cache(self, batch, extras):
        cfg = self.cfg
        kvdt = self.kv_dtype()
        # k/v [L, B, S, KV, hd] as the layer scan collects them, in the
        # order the decode step reads them
        stored = lambda kv: (kv.swapaxes(2, 3) if kv_major(cfg) else kv).astype(kvdt)
        if cfg.family in ("dense", "moe"):
            k, v = extras
            return {"k": stored(k), "v": stored(v)}
        if cfg.family == "ssm":
            conv, ssm = extras
            return {"conv": conv, "ssm": ssm}
        if cfg.family == "hybrid":
            (rec_extras, attn_extras) = extras
            conv, rec = rec_extras
            k, v = attn_extras
            W = cfg.local_window
            # keep the last W positions; decode continues the ring at pos % W,
            # so position p must sit at slot p % W — roll the slice to align.
            S = k.shape[2]
            if S > W:
                k = jnp.roll(k[:, :, -W:], shift=S % W, axis=2)
                v = jnp.roll(v[:, :, -W:], shift=S % W, axis=2)
            return {"conv": conv, "rec": rec, "k": stored(k), "v": stored(v)}
        if cfg.family == "encdec":
            dec_extras, _enc_out = extras
            self_kv, cross_kv = dec_extras
            k, v = self_kv
            ck, cv = cross_kv
            return {
                "k": stored(k),
                "v": stored(v),
                "cross_k": ck.astype(kvdt),
                "cross_v": cv.astype(kvdt),
            }
        raise ValueError(cfg.family)

    def decode_step(self, params, cache, tokens, pos, mesh_info=None):
        """One decode step. tokens [B, 1] int32; pos: scalar position."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        if cfg.family == "encdec":  # absolute positions (whisper)
            posarr = jnp.full((1, 1), pos, jnp.int32)
            x = x + sinusoidal_embedding(posarr, cfg.d_model).astype(x.dtype)
        if cfg.family in ("dense", "moe"):
            h, cache = decode_stack(params, cfg, x, cache, pos, mesh_info)
        elif cfg.family == "ssm":
            h, cache = decode_ssm(params, cfg, x, cache, mesh_info)
        elif cfg.family == "hybrid":
            h, cache = decode_hybrid(params, cfg, x, cache, pos, mesh_info)
        elif cfg.family == "encdec":
            h, cache = decode_encdec(params, cfg, x, cache, pos, mesh_info)
        else:
            raise ValueError(cfg.family)
        h = self._final_norm(params, h)
        with scopes.scope("logits"):
            return self.logits(params, h)[..., : cfg.vocab_size], cache

    # -- cache templates (for the decode dry-run input specs) -------------------

    def kv_dtype(self):
        cfg = self.cfg
        return jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)

    def abstract_cache(self, batch_size: int, seq_len: int) -> dict:
        """The decode cache.  Self-attention k/v stack the layers' [B, KV,
        S, hd] where the Pallas kernel reads them (``kv_major``), their [B,
        S, KV, hd] elsewhere; whisper's cross k/v are always [L, B, F, KV,
        hd]."""
        cfg = self.cfg
        kvdt = self.kv_dtype()  # k/v caches (may be quantized, e.g. fp8)
        cdt = jnp.dtype(cfg.compute_dtype)  # conv tails / recurrent states
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        kv = lambda n, S: (n, batch_size) + ((KV, S) if kv_major(cfg) else (S, KV)) + (hd,)
        if cfg.family in ("dense", "moe"):
            shp = kv(L, seq_len)
            return {"k": jax.ShapeDtypeStruct(shp, kvdt), "v": jax.ShapeDtypeStruct(shp, kvdt)}
        if cfg.family == "ssm":
            return {
                "conv": jax.ShapeDtypeStruct(
                    (L, batch_size, cfg.ssm_conv - 1, cfg.d_inner), cdt
                ),
                "ssm": jax.ShapeDtypeStruct(
                    (L, batch_size, cfg.d_inner, cfg.ssm_state), jnp.float32
                ),
            }
        if cfg.family == "hybrid":
            kinds = [cfg.block_pattern[i % len(cfg.block_pattern)] for i in range(L)]
            n_rec, n_attn = kinds.count("rec"), kinds.count("attn")
            W = min(cfg.local_window, seq_len)
            return {
                "conv": jax.ShapeDtypeStruct(
                    (n_rec, batch_size, cfg.ssm_conv - 1, cfg.lru_width), cdt
                ),
                "rec": jax.ShapeDtypeStruct((n_rec, batch_size, cfg.lru_width), jnp.float32),
                "k": jax.ShapeDtypeStruct(kv(n_attn, W), kvdt),
                "v": jax.ShapeDtypeStruct(kv(n_attn, W), kvdt),
            }
        if cfg.family == "encdec":
            shp = kv(L, seq_len)
            cshp = (L, batch_size, cfg.enc_positions, KV, hd)
            return {
                "k": jax.ShapeDtypeStruct(shp, kvdt),
                "v": jax.ShapeDtypeStruct(shp, kvdt),
                "cross_k": jax.ShapeDtypeStruct(cshp, kvdt),
                "cross_v": jax.ShapeDtypeStruct(cshp, kvdt),
            }
        raise ValueError(cfg.family)


@partial(jax.jit, static_argnums=0)
def _init_params(cfg: ModelConfig, rng) -> dict:
    """All leaves in one program: eager init compiles and runs one small
    program per leaf shape (about a minute for a 4B model on a v5e)."""
    return init_from_template(build_template(cfg), rng, jnp.dtype(cfg.param_dtype))


def _ce_loss(logits, targets):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
