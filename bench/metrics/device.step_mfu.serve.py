"""Model FLOPs of the prompts and tokens served by the window's close
(matmuls, causal attention, the LM head where a logit is used) over the
window times the chips' bf16 peak, percent.  Below the knee the offered
rate sets the work, so this reads the load more than the program."""
from benchlib.readers import step_mfu


def read(run):
    return step_mfu(run)
