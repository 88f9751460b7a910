"""The first layer's share of its roofline, in percent, from the device trace.

The operations counted are found by what they touch, not by a fusion's number:
every device operation one of whose arrays is feature-wide (``work.touches_features``),
which only ``w1`` and a batch of feature rows are. Their time in the window is set against
the least time the chip could take for the rows the requests asked for (``work.py``).
"""
import peaks
import work


def read(run):
    t, cfg = run["trace"], run["cfg"]
    if not t.devices or run["rehearsal"] or not run["rows"]:
        return None
    seconds = t.op_seconds(lambda text: work.touches_features(text, cfg["features"]))
    if seconds <= 0:
        return None
    f, h = cfg["features"], cfg["hidden"]
    least = work.roofline_seconds(work.ff_layer1_flops(run["rows"], f, h),
                                  work.ff_layer1_bytes(run["rows"], f, h, run["requests"]),
                                  peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
