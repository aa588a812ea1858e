"""Model FLOPs of the steps finished in the window (3x the forward:
matmuls, the LM head, causal attention; recomputation not counted) over
the window times the chips' bf16 peak, percent."""
from benchlib.readers import step_mfu


def read(run):
    return step_mfu(run)
