"""Device milliseconds of admission prefill per thousand padded prompt
tokens (``programs.prefill_ms_per_ktok``)."""

import programs


def read(ctx):
    return programs.prefill_ms_per_ktok(ctx)
