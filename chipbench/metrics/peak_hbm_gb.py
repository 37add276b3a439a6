"""Peak device memory in use over the run (``peak_bytes_in_use`` of the
fullest of the cell's chips, read after the window), in GB. Warm-up runs
the cell's largest programs, so this is what a deployment has to
provision on each chip."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
