"""Device: 1 - (union of the device's operation intervals / traced
window), mean over the cell's devices, in %."""


def read(window, trace, config, peaks):
    return 100.0 * trace["idle_share"] if trace else None
