"""90th percentile of time to first token over every request due in the
window, in a batch cell: the reader of ttft_p90_s.chat."""
from harness.spec import reader

read = reader("ttft_p90_s.chat")
