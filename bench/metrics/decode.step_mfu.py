"""The decode step's share of the chip's bf16 peak: the model FLOPs of
the window's decode steps (matmuls, the LM head, attention over each
live prefix) over the time those steps took (host spans ended by
block_until_ready) times the chips' peak, percent.  It bounds the
flash-decode kernel's roofline from above the kernel."""


def read(run):
    secs = sum(run.spans.durations.get("model.decode_step", ()))
    flops = run.counters.get("decode_flops")
    peak = run.peaks.get("bf16_flops")
    if not secs or not flops or not peak:
        return None
    return 100.0 * flops / (secs * run.chips * peak)
