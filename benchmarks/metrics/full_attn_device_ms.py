"""Device milliseconds a step under the program's ``full_attn`` scope (the
full-attention layers' projections, q/k norm and causal-within-document
attention: forward, rematerialised forward and backward;
``lib/scopes.py``).  Layer: kernels.  Nothing where the trace or the program
has no such scope."""


def read(ctx):
    return (ctx.get("scope_ms") or {}).get("full_attn")
