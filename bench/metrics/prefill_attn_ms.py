"""Device ms per prefill in attention: the kernel call (scope
``attn_core``) and the re-lay of q, k and v around it (``kv_layout``), over
the traced ``jit_prefill`` runs."""

from bench.scope_trace import scope_ms

PROGRAM = "jit_prefill"
SCOPES = ("attn_core", "kv_layout")


def read(run):
    return None if run.trace is None else scope_ms(run.trace, PROGRAM, SCOPES)
