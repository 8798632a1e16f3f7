"""collective_s: device seconds per step in collective operations
(all-reduce, collective-permute, all-gather, reduce-scatter, all-to-all,
with their start and done halves), the mean over the cell's chips."""


def read(ctx):
    red, win = ctx["trace"], ctx["window"]
    if not red.chips or win.steps == 0:
        return None
    total = sum(c.collective_s for c in red.chips) / len(red.chips)
    if total <= 0:
        return None
    return total / win.steps
