"""Share of the traced window (window and drain) with no op on the device,
in %: 1 - union of device-op intervals / window length."""


def read(ctx):
    red = ctx["trace"]
    if not red["devices"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
