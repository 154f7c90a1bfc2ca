"""The 95th percentile of the window's call times, in milliseconds: from
the call's start with host bytes in hand to its result ready (on the
host for bulk, on the device for ingest), over every call of the
window, by the host clock."""

import statistics


def read(obs):
    lat = obs.latencies_s
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=20, method="inclusive")[-1] * 1e3
