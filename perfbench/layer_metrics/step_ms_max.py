"""Entry points: the largest interval between two successive step
completions in the window, in ms (the tail of a window too short for a
95th percentile)."""


def read(window, trace, config, peaks):
    return 1e3 * max(window["intervals_s"]) if window["intervals_s"] else None
