"""Mean share of the decode slots that held an active row, per decode
tick (the engine's ``occupancy_sum / ticks / max_batch``), over the
traced window."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "tokens_per_s"


def read(ctx):
    ticks = ctx.engine.get("ticks", 0)
    if ticks <= 0:
        return None
    return 100.0 * ctx.engine["occupancy_sum"] / ticks / ctx.max_batch
