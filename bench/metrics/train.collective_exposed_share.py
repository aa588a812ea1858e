"""Share of the traced training window in which the chips wait on an
exchange between them, percent: the time of synchronous collectives
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all)
and of the -done halves of asynchronous ones (a -done runs while its chip
waits for the collective to land; a transfer hidden behind compute is not
counted), each chip's own time by HLO operation name, averaged over the
chips.  Nothing to read on one chip."""
COLLECTIVE = (r"^(all-reduce|all-gather|reduce-scatter|collective-permute"
              r"|all-to-all)(-done)?$|^async-collective-done$")


def read(run):
    tr = run.trace
    if tr is None or tr.chips < 2 or tr.window_s <= 0:
        return None
    secs, _ = tr.op_seconds(COLLECTIVE)
    return 100.0 * secs / tr.window_s
