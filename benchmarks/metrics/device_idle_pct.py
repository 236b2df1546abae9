"""Share of the traced stretch in which NO operation ran on the device:
1 - union of the device-op intervals / span from the first to the last device
event, mean over the chips.  Layer: device."""


def read(ctx):
    s = ctx["summary"]
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * s.idle_share
