"""Device ms per decode step that the layer scan spends on the weights
themselves (scope ``layers``: each layer's slices of the stacked weights and
their re-lay), over the traced ``jit_decode_step`` runs."""

from bench.scope_trace import scope_ms

PROGRAM = "jit_decode_step"
SCOPES = ("layers",)


def read(run):
    return None if run.trace is None else scope_ms(run.trace, PROGRAM, SCOPES)
