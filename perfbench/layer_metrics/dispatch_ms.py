"""Entry points: host clock around the step call until it returns (the
enqueue), mean over every step of the window, in ms."""


def read(window, trace, config, peaks):
    calls = window["dispatch_s"]
    return 1e3 * sum(calls) / len(calls) if calls else None
