"""Device idle share of the traced window, in percent: 1 - (union of the
device-busy intervals) / window, averaged over the chips used."""


def read(r):
    from benchmark.trace import idle_share

    return 100.0 * idle_share(r.trace, r.lo, r.hi)
