"""Device ms per decode step in the linear layers (scopes ``embed``,
``qkv``, ``attn_out``, ``ffn``, ``logits``, with the norms and residual adds
inside them), over the traced ``jit_decode_step`` runs."""

from bench.scope_trace import scope_ms

PROGRAM = "jit_decode_step"
SCOPES = ("embed", "qkv", "attn_out", "ffn", "logits")


def read(run):
    return None if run.trace is None else scope_ms(run.trace, PROGRAM, SCOPES)
