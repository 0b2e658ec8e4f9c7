"""2 x multiply-adds of VGG-16 per image, times the images of the
forwards that ran inside the traced window (a forward cut by the
window's edge counts by the share of it inside), over the window, over
the int8 peak."""

from bench import trace


def read(rec):
    tr, w = rec.get("trace"), rec["work"]
    if not tr or "forward_module" not in w:
        return None
    lo, hi = rec["window_ns"]
    forwards = sum(
        (min(hi, m[2]) - max(lo, m[1])) / (m[2] - m[1])
        for m in tr["device"][0]["modules"]
        if trace.module_name(m[0]) == w["forward_module"]
        and m[1] < hi and m[2] > lo and m[2] > m[1])
    if forwards <= 0:
        return None
    ops = 2.0 * forwards * w["batch"] * w["macs_per_image"]
    return 100.0 * ops / ((hi - lo) / 1e9) / rec["peaks"]["int8_ops_per_s"]
