"""Continuous-batching serving scheduler.

Production serving at pod scale keeps the decode batch full: finished
sequences release their KV-cache slot and queued requests are prefilled into
it while the other slots keep decoding (continuous batching).  This
scheduler implements the slot machinery over the Model prefill/decode steps:

  * a fixed pool of ``batch_size`` slots, each owning a segment of the
    static-shape KV cache;
  * per-slot position counters (sequences at different offsets decode in the
    same step — the attention mask is per-slot via kv_len);
  * admission: new requests are prefilled one-at-a-time into a free slot's
    cache segment (single-sequence prefill, batched decode — the standard
    disaggregation-lite layout);
  * completion by EOS token or max_new_tokens.

CAPre connection: the decode step's access plan is batch-shape-static, so
the scheduler's steady state keeps the prefetch schedule valid regardless
of request churn — exactly why the plan is derived per (shape, batch) and
not per request.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import kv_major


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [S] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # filled by the scheduler
    output: list = field(default_factory=list)
    done: bool = False


@dataclass
class _Slot:
    busy: bool = False
    req: Optional[Request] = None
    pos: int = 0  # next write position in this slot's cache segment
    generated: int = 0


class ContinuousBatcher:
    """Slot-based continuous batching over a Model.

    The KV cache is [L, B, S_max, KV, hd]; slot i owns batch row i.  For
    simplicity each admitted prompt is prefilled with a batch-1 prefill and
    its cache rows are copied into the slot (real deployments run a
    dedicated prefill worker; the copy is the slot hand-off either way)."""

    def __init__(self, model, params, batch_size: int, max_len: int):
        self.model = model
        self.params = params
        self.B = batch_size
        self.max_len = max_len
        self.slots = [_Slot() for _ in range(batch_size)]
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        cfg = model.cfg
        kvdt = model.kv_dtype()
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        self.cache = {
            "k": jnp.zeros((L, batch_size, max_len, KV, hd), kvdt),
            "v": jnp.zeros((L, batch_size, max_len, KV, hd), kvdt),
        }
        self._decode = jax.jit(
            lambda p, c, t, lens: self._decode_step(p, c, t, lens)
        )
        self._prefill = jax.jit(lambda p, b: model.prefill(p, b))
        self.steps = 0

    # -- batched decode with per-slot positions -----------------------------

    def _decode_step(self, params, cache, tokens, kv_lens):
        """One decode step where every slot sits at its own position.

        Uses the per-slot valid-length mask (kv_lens [B]) instead of a single
        scalar pos; new k/v are written at each slot's own position."""
        model, cfg = self.model, self.model.cfg
        from repro.models.layers import apply_norm, apply_rope, qkv_project, attn_output
        from repro.models.transformer import cfg_dtype, ffn_block

        dt = cfg_dtype(cfg)
        x = model.embed(params, tokens)
        B = tokens.shape[0]
        positions = kv_lens[:, None]  # [B, 1] current index per slot

        def body(h, inp):
            lp, kc, vc = inp
            hn = apply_norm(cfg.norm, h, lp["ln1"], lp.get("ln1_b"))
            q, k, v = qkv_project(hn, lp["attn"], cfg, dt)
            pos_arr = positions
            if cfg.rope == "mrope":
                pos_arr = jnp.broadcast_to(positions[None], (3, B, 1))
            q = apply_rope(cfg.rope, q, pos_arr, cfg.rope_theta)
            k = apply_rope(cfg.rope, k, pos_arr, cfg.rope_theta)
            # per-slot scatter of the new kv at its own position
            onehot = jax.nn.one_hot(kv_lens, kc.shape[1], dtype=kc.dtype)  # [B, S]
            kc = kc * (1 - onehot)[..., None, None] + onehot[..., None, None] * k.astype(kc.dtype)
            vc = vc * (1 - onehot)[..., None, None] + onehot[..., None, None] * v.astype(vc.dtype)
            # attend with per-slot valid length
            S = kc.shape[1]
            KV = cfg.n_kv_heads
            G = cfg.n_heads // KV
            q5 = q.reshape(B, 1, KV, G, cfg.head_dim)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q5, kc.astype(dt),
                           preferred_element_type=jnp.float32) / (cfg.head_dim ** 0.5)
            valid = jnp.arange(S)[None, :] <= kv_lens[:, None]  # [B, S]
            s = jnp.where(valid[:, None, None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p, vc.astype(dt)).reshape(B, 1, cfg.q_dim)
            h = h + o.reshape(B, 1, cfg.n_heads, cfg.head_dim).reshape(B, 1, cfg.q_dim) @ lp["attn"]["wo"].astype(dt)
            h = ffn_block(h, lp, cfg, dt, None)
            return h, (kc, vc)

        h, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
        h = model._final_norm(params, h)
        logits = model.logits(params, h)[..., : cfg.vocab_size]
        return logits, {"k": k_new, "v": v_new}

    # -- admission -----------------------------------------------------------

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for i, slot in enumerate(self.slots):
            if slot.busy or not self.queue:
                continue
            req = self.queue.popleft()
            S = len(req.prompt)
            batch = {"inputs": jnp.asarray(req.prompt, jnp.int32)[None]}
            logits, cache1 = self._prefill(self.params, batch)
            if kv_major(self.model.cfg):  # the batcher's own decode reads [L, B, S, KV, hd]
                cache1 = {key: x.swapaxes(2, 3) for key, x in cache1.items()}
            # hand the prefilled rows to the slot's cache segment
            pad = self.max_len - cache1["k"].shape[2]
            for key in ("k", "v"):
                seg = jnp.pad(cache1[key], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                self.cache[key] = self.cache[key].at[:, i : i + 1].set(
                    seg.astype(self.cache[key].dtype)
                )
            slot.busy = True
            slot.req = req
            slot.pos = S
            slot.generated = 0
            tok = int(jnp.argmax(logits[0, -1]))
            req.output.append(tok)
            slot.generated = 1

    # -- one engine tick -------------------------------------------------------

    def step(self) -> int:
        """Admit + one batched decode step. Returns number of active slots."""
        self._admit()
        active = [s for s in self.slots if s.busy]
        if not active:
            return 0
        tokens = np.zeros((self.B, 1), np.int32)
        lens = np.zeros((self.B,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot.busy:
                tokens[i, 0] = slot.req.output[-1]
                lens[i] = slot.pos
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(lens)
        )
        nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1), np.int32)
        for i, slot in enumerate(self.slots):
            if not slot.busy:
                continue
            slot.pos += 1
            slot.generated += 1
            req = slot.req
            tok = int(nxt[i])
            req.output.append(tok)
            eos = req.eos_id is not None and tok == req.eos_id
            if eos or slot.generated >= req.max_new_tokens or slot.pos >= self.max_len - 1:
                req.done = True
                self.finished.append(req)
                slot.busy = False
                slot.req = None
        self.steps += 1
        return len([s for s in self.slots if s.busy])

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        while (self.queue or any(s.busy for s in self.slots)) and self.steps < max_steps:
            self.step()
        return self.finished
