"""``device_idle_share.lib``: 1 - device busy / traced window, in the
library's closed loop.  Busy is the union of the device's op intervals."""

from chipbench.metrics._device import idle_share


def read(r):
    return idle_share(r.trace)
