"""Share of decode slot-steps in the window that held a live request, in a
batch cell: the reader of slot_busy_frac.chat."""
from harness.spec import reader

read = reader("slot_busy_frac.chat")
