"""device_idle.* (%): the share of the traced stretch in which no op ran on
the device, mean over the chips used."""

from chipbench.readers import device_idle as read  # noqa: F401
