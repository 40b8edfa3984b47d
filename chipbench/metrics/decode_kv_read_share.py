"""Share of the KV positions decode attention could read that it did
read, over the window: delta ``decode_kv_positions_read`` / delta
``decode_kv_positions_full`` from the engine's counters (full is slots x
max_len per decode step; read counts the blocks the length-aware kernel
fetched below each live slot's length). A program without those counters
reads nothing."""


def read(ctx):
    full = ctx.delta("decode_kv_positions_full")
    if full <= 0:
        return None
    return ctx.delta("decode_kv_positions_read") / full
