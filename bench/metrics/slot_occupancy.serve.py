"""Mean share of the server's slots in flight per round: the mean of the
``round.slot_utilization`` observations made inside the window."""


def read(ctx):
  n = ctx.measures.get("slot_util_count", 0)
  if not n:
    return None
  return 100.0 * ctx.measures["slot_util_sum"] / n
