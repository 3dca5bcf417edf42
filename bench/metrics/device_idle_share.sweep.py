"""device_idle_share.sweep: the share of the traced window in which the chip
ran no operation, in a sweep cell (1 − union of device-op intervals ÷ window)."""


def read(ctx):
    if ctx.device is None or ctx.mix["kind"] != "sweep" or not ctx.device.devices:
        return None
    return 100.0 * ctx.device.idle_share()
