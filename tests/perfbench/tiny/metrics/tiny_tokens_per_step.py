"""A per-layer metric added by files alone: tokens emitted per engine step
in the window, from the benchmark's own counts."""


def read(L):
    steps = L.numbers["steps_in_window"]
    return L.numbers["tokens_in_window"] / steps if steps else None
