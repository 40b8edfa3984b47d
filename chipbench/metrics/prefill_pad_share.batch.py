"""Share of the token rows prefill computed in the window that were
padding: 1 - real prompt tokens admitted / rows x bucket length of every
prefill dispatch, from the engine's counters ``prefill_prompt_tokens`` and
``prefill_computed_tokens``. A program without those counters reads
nothing."""


def read(ctx):
    computed = ctx.delta("prefill_computed_tokens")
    if computed <= 0:
        return None
    return 1.0 - ctx.delta("prefill_prompt_tokens") / computed
