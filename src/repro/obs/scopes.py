"""Named scopes of the served steps.

The model step marks the device work of each of its layers with
``jax.named_scope`` under one of the fixed names below.  A scope is metadata
only: it lands in each compiled op's ``op_name`` (the ``tf_op`` of a
profiler trace, e.g. ``jit(decode_step)/layers/while/body/kv_write/
dynamic_update_slice``) and changes no op.  Scopes nest; an op belongs to
the innermost one on its path, and ops of the layer scan under no other
scope (slicing each layer's weights and cache out of the stacks, stacking
its outputs back) belong to ``layers``.
"""

from __future__ import annotations

import jax

SCOPES = (
    "embed",  # token gather
    "layers",  # the layer scan's own ops
    "qkv",  # norm, QKV projection, rope
    "kv_write",  # writing K/V into the cache
    "kv_layout",  # laying the cache out for the attention kernel
    "attn_core",  # the attention kernel, or its jnp fallback
    "attn_out",  # output projection and its residual add
    "ffn",  # norm, feed-forward and its residual add
    "logits",  # final norm, head matmul, vocab slice
)


def scope(name: str):
    """``jax.named_scope(name)`` for a name of ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of {SCOPES}")
    return jax.named_scope(name)
