"""Share of the traced window in which no operation ran on the device, in
%, in a batch cell: the reader of device_idle_share.chat."""
from harness.spec import reader

read = reader("device_idle_share.chat")
