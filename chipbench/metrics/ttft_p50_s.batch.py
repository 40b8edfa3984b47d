"""Median time to first token over every request due in the window, in a
batch cell: the reader of ttft_p50_s.chat. In a closed loop a request is
due when its client sends it, so this is the server's queue and prefill."""
from harness.spec import reader

read = reader("ttft_p50_s.chat")
