"""device_idle_share.distributed: the share of the traced window in which
the chips ran no operation, in a distributed cell (1 - union of device-op
intervals / window, averaged over the chips)."""


def read(ctx):
    if ctx.device is None or ctx.mix["kind"] != "distributed" or not ctx.device.devices:
        return None
    return 100.0 * ctx.device.idle_share()
