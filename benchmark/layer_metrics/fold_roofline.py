"""The folds' share of their roofline, in percent, from the device trace.

A fold reads each of its columns once, so the least time is the bytes of the
columns the window's queries scanned over the HBM bandwidth. The time is every
device operation's in the window: in this deployment the device runs nothing
but the folds' steps (transfers from the host are not device operations).
"""
import peaks
import work


def read(run):
    t, cfg = run["trace"], run["cfg"]
    if not t.devices or run["rehearsal"] or not run["requests"]:
        return None
    seconds = t.op_seconds(lambda text: True)
    if seconds <= 0:
        return None
    columns = sum(cfg["query_columns"].values())     # a request is one of each query
    nbytes = work.fold_bytes(cfg["rows"], columns) * run["requests"]
    return 100.0 * nbytes / peaks.peaks_for(run["device_kind"])["hbm_bytes_per_s"] / seconds
