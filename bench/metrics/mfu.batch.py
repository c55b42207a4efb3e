"""Model FLOPs of the work served in the window (every prompt token
prefilled and output token produced, counted by the configuration's
architecture) over the window and the chip's bf16 peak."""
from benchlib.readers import mfu_percent


def read(run):
    return mfu_percent(run)
