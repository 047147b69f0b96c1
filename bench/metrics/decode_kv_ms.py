"""Device ms per decode step in the KV cache's own ops: the in-place write
of the new position (scope ``kv_write``) and any re-lay of K/V for the
kernel (``kv_layout``), summed over the traced ``jit_decode_step`` runs."""

from bench.scope_trace import scope_ms

PROGRAM = "jit_decode_step"
SCOPES = ("kv_write", "kv_layout")


def read(run):
    return None if run.trace is None else scope_ms(run.trace, PROGRAM, SCOPES)
