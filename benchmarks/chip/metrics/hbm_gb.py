"""Device memory the round program the window runs needs: the compiler's
peak for the compiled K-round segment (``memory_analysis()``), which holds
the donated state, the segment's tokens and every temporary. It sets how
many clients, or how large a batch, one chip can hold."""

UNIT = "GB"


def read(ctx):
    return ctx["job"].peak_bytes / 1e9
