"""Share of the traced window in which no operation ran on the device
(1 - union of the op intervals / window), in %, averaged over the chips.
The same reader serves every cell: ``device_idle.<cell kind>``."""


def read(red, ctx):
    if red.busy_s() <= 0:
        return None
    return 100.0 * red.idle_share()
