"""Device milliseconds per engine superstep: the device's busy time in the
traced window over the supersteps its runs made, where the program's entry
fixes that count (PageRank's sweeps).  The host's share of a run is left
out, so this moves apart from ``teps`` when the host holds the device up."""


def read(ctx):
  steps = ctx.measures.get("supersteps", 0)
  if ctx.summary is None or not steps or ctx.summary.busy_s <= 0:
    return None
  return 1e3 * ctx.summary.busy_s / steps
