"""90th percentile of time per output token after the first chunk, in a
batch cell: the reader of tpot_p90_ms.chat."""
from harness.spec import reader

read = reader("tpot_p90_ms.chat")
