"""Milliseconds per server round: the window over the server's ``rounds``
counter (its increase inside the window)."""


def read(ctx):
  rounds = ctx.measures.get("rounds", 0)
  if not rounds:
    return None
  return 1e3 * ctx.measures["window_s"] / rounds
