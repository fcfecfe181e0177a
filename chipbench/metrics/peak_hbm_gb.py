"""Peak HBM of the fullest chip, in GB: its arrays at their peak plus the
runtime's reserve for the step's temporaries (``harness.peak_bytes``),
read after the window."""


def read(run):
    return run.peak_bytes / 1e9
