"""The transcode kernels' share of the HBM roofline, from the profiler
trace.

The bytes are what the window's launches need at the least (each call's
live input read once and its output units written once,
``common.kernel_bytes``, summed by the harness over the calls whose
input runs a kernel).  The time is the summed device time of the
window's Pallas kernels (``tpu_custom_call`` ops; the cells' paths
launch no Pallas kernel but the transcode ones); on several chips it is
summed over them too, so the share is of one chip's peak per
chip-second.  Nothing when the window ran no kernel.
"""

import devtrace


def read(obs):
    if obs.trace is None or not obs.kernel_bytes:
        return None
    events = obs.trace.kernels()
    if not events:
        return None
    kernel_s = sum(e.end - e.start for e in events) / 1e9
    return devtrace.roofline_pct(obs.kernel_bytes,
                                 obs.peak["hbm_bytes_per_s"], kernel_s)
