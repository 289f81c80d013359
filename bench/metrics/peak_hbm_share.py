"""Device: the process's peak bytes in use over the device's byte limit,
from `memory_stats()` read after the window.  The peak covers set-up
too; the warm-up runs the window's own segment and prefill programs,
and the weights' init-and-quantize program holds less."""


def read(ctx):
    peak = ctx.memory.get("peak_bytes_in_use")
    limit = ctx.memory.get("bytes_limit")
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
