"""Peak device memory in use over the run (``peak_bytes_in_use`` of the
device, read after the window), in GB. Warm-up runs the cell's largest
programs, so this is what a deployment has to provision."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None
