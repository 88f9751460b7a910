"""The whole score's share of the chip's bf16 peak: both products' FLOP over the traced window, in percent."""
import peaks
import work


def read(run):
    t, cfg = run["trace"], run["cfg"]
    if not t.devices or t.window_s <= 0 or run["rehearsal"]:
        return None
    flops = work.ff_score_flops(run["rows"], cfg["features"], cfg["hidden"], cfg["labels"])
    return 100.0 * flops / (t.window_s * peaks.peaks_for(run["device_kind"])["bf16_flops_per_s"])
