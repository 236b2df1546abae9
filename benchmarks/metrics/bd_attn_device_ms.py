"""Device milliseconds a step under the program's ``bd_attn`` scope (block
diffusion's masked blockwise products and online softmax of every layer:
forward, the layer's rematerialised forward and backward), by exclusive
time of the operations whose ``tf_op`` names the scope (``lib/scopes.py``).
Layer: kernels.  Nothing where the trace or the program has no such
scope."""


def read(ctx):
    return (ctx.get("scope_ms") or {}).get("bd_attn")
