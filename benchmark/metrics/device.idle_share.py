"""Share of the traced span in which no operation ran on the first
device. Layer: device."""


def read(ctx):
    tr = ctx["trace"]
    if tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.device_busy_s(tr.first_device()) / tr.window_s)
