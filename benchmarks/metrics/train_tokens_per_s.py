"""Tokens a second through the window's epochs: the program's own
``lm_tokens`` counter (``obs.trace.tally`` in ``Trainer``'s stream of packed
sequences) over its ``loop_s``, summed over the window's epochs.  Layer:
whole step.  Nothing where the program keeps no such counter."""


def read(ctx):
    return ctx.get("tokens_per_s")
