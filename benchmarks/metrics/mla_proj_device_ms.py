"""Device milliseconds a step under the program's ``mla_proj`` scope (latent
attention's projections: ``W_q``, ``W_kv_a``, the latent's norm, ``W_kv_b``
and ``W_o``, forward, rematerialised forward and backward), by exclusive
time of the operations whose ``tf_op`` names the scope (``lib/scopes.py``):
the latent's own cost, apart from the attention core.  Layer: kernels.
Nothing where the trace or the program has no such scope."""


def read(ctx):
    return (ctx.get("scope_ms") or {}).get("mla_proj")
