"""Examples a second the program's own train stream delivers to the device
with NO step consuming them: one epoch of ``Trainer._train_batches`` drained
after the window, host clock.  Layer: input.  The ceiling the loader puts on
``train_examples_per_s``."""


def read(ctx):
    return ctx["loader_examples_per_s"]
