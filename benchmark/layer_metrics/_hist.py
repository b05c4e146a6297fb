"""Percentile of one of the gateway's flat cumulative histograms
(``<prefix>_le_<bound>``, ``_count``), over the window: the difference of two
heartbeat snapshots, interpolated inside the bucket."""


def percentile(delta, prefix, q):
    total = delta.get(prefix + "_count")
    if not total:
        return None
    bounds = sorted(int(k[len(prefix) + 4:]) for k in delta
                    if k.startswith(prefix + "_le_"))
    want, below, low = q * total, 0, 0
    for b in bounds:
        upto = delta[prefix + "_le_%d" % b]
        if upto >= want:
            inside = upto - below
            return low + (b - low) * ((want - below) / inside if inside else 1)
        below, low = upto, b
    return float(bounds[-1])
