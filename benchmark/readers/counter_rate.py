"""The rate of one of the engine's counters over the window: what it
counted between the reading that opened the window and the one that closed
it (both taken between two decode blocks, each with its time), over the
time between them. Counted where the tokens are made, all of them."""


def read(ctx, counter):
    w = ctx["window"]
    dt = w["close"]["now"] - w["open"]["now"]
    if dt <= 0:
        return None
    return (w["close"][counter] - w["open"][counter]) / dt
