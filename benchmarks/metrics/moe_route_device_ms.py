"""Device milliseconds a step under the program's ``moe_route`` scope (every
``E`` layer's router: the product over all published experts, sigmoid, the
``top_k`` of the biased scores, the chosen set and its normalised weights;
forward, the layer's rematerialised forward and backward), by exclusive time
of the operations whose ``tf_op`` names the scope (``lib/scopes.py``).
Layer: kernels.  Nothing where the trace or the program has no such scope."""


def read(ctx):
    return (ctx.get("scope_ms") or {}).get("moe_route")
