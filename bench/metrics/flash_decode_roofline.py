"""The paged flash-decode kernel's share of its roofline in the traced
stretch: the K/V rows of the live prefixes (and queries and outputs) at
the HBM peak, over the time its operations took, percent."""
from benchlib.readers import roofline


def read(run):
    return roofline(run, r"decode_attention|flash_decode|_decode_kernel",
                    "traced_decode_attn_bytes")
