"""Host seconds of ``Server.plan(batch)``: the CAPre access plan of one
decode step, derived from its jaxpr during set-up."""


def read(run):
    return run.plan_derive_s
